"""Reduce a profiler trace to the benchmark's device numbers.

The traced run wraps its window in a host span ``bench.window`` and each
call into the program in spans of its own (``bench.step``,
``bench.factorize``, ``bench.solve``, ``bench.sweep``), written with
``jax.profiler.TraceAnnotation``.  The program's jitted programs carry no
stable names yet, so device time is attributed by the host span open around
each call, not by program name.

Device busy time is the union of the intervals in which an operation ran
on a device (the ``XLA Ops`` line of the ``/device:TPU:<id>`` plane of each
device the cell used), read inside the window; numbers over several devices
are means over them.  A used device whose plane or ``XLA Ops`` line is
missing is an error: no other line (``XLA Modules`` spans whole programs,
stalls included) stands in for it.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

import numpy as np

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)")
OP_LINE = "XLA Ops"


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {directory}, "
                           f"found {len(paths)}")
    return paths[0]


def union(starts, ends):
    """Merge intervals into sorted disjoint ``(starts, ends)`` arrays."""
    starts = np.asarray(starts, dtype=np.float64)
    ends = np.asarray(ends, dtype=np.float64)
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.empty(s.size, dtype=bool)
    new[0] = True
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.maximum.reduceat(e, idx)


def covered(ms, me, lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` covered by the disjoint sorted intervals."""
    if hi <= lo or ms.size == 0:
        return 0.0
    i = int(np.searchsorted(me, lo, side="right"))
    j = int(np.searchsorted(ms, hi, side="left"))
    if j <= i:
        return 0.0
    s = np.clip(ms[i:j], lo, hi)
    e = np.clip(me[i:j], lo, hi)
    return float(np.sum(e - s))


def gaps(ms, me, lo: float, hi: float):
    """Idle intervals of ``[lo, hi]`` between the disjoint busy intervals."""
    s = np.clip(ms, lo, hi)
    e = np.clip(me, lo, hi)
    starts = np.concatenate([[lo], e])
    ends = np.concatenate([s, [hi]])
    keep = ends > starts
    return starts[keep], ends[keep]


def _events(line):
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events]


def op_name(name: str) -> str:
    """``fusion.239`` of an ``XLA Ops`` event named by its HLO text
    (``%fusion.239 = (f32[...]) fusion(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def read(path: str, device_ids):
    """``(spans, devices)`` from an ``.xplane.pb`` file.

    ``spans``: ``[(name, start_ns, end_ns)]`` of the benchmark's host spans.
    ``devices``: ``{plane name: [(op name, start_ns, end_ns)]}`` for the
    devices ``device_ids`` (JAX device ids) and no others.
    """
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    want = {int(i) for i in device_ids}
    spans, devices = [], {}
    for plane in pd.planes:
        m = _DEVICE_PLANE.fullmatch(plane.name)
        if m and int(m.group(1)) in want:
            lines = {ln.name: ln for ln in plane.lines}
            if OP_LINE not in lines:
                raise ValueError(f"{plane.name} has no {OP_LINE!r} line; "
                                 f"its lines are {sorted(lines)}")
            devices[plane.name] = [(op_name(n), s, e)
                                   for n, s, e in _events(lines[OP_LINE])]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend(e for e in _events(ln)
                             if e[0].startswith(SPAN_PREFIX))
    missing = want - {int(_DEVICE_PLANE.fullmatch(p).group(1))
                      for p in devices}
    if missing:
        raise ValueError(f"no device plane for device ids {sorted(missing)}")
    return spans, devices


def reduce(spans, devices, top: int = 10) -> dict:
    """Busy time, per-span device time and the breakdown of one window.

    Returns a dict with ``window_s``; ``busy_s`` (mean over devices) and
    ``busy_s_by_device``; ``span_device_s`` (name -> mean over devices of
    the device busy seconds inside that span's instances) and
    ``span_count``; ``device_ops`` (the ``top`` op names by device seconds,
    mean over devices) and ``idle_gaps`` (the ``top`` longest idle gaps of
    the first device, each named by the innermost benchmark span open at
    its midpoint).
    """
    windows = [s for s in spans if s[0] == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    if not devices:
        raise ValueError("no device plane with operations in the trace")
    _, lo, hi = windows[0]
    inner = [s for s in spans if s[0] != WINDOW and s[1] < hi and s[2] > lo]
    names = sorted(devices)
    merged = {}
    busy = {}
    op_time = defaultdict(float)
    for dev in names:
        evs = [e for e in devices[dev] if e[2] > lo and e[1] < hi]
        st = np.array([e[1] for e in evs], dtype=np.float64)
        en = np.array([e[2] for e in evs], dtype=np.float64)
        merged[dev] = union(st, en)
        busy[dev] = covered(*merged[dev], lo, hi) * 1e-9
        for name, s, e in evs:
            op_time[name] += (min(e, hi) - max(s, lo)) * 1e-9 / len(names)
    span_s = defaultdict(float)
    span_n = defaultdict(int)
    for name, s, e in inner:
        span_n[name] += 1
        for dev in names:
            span_s[name] += covered(*merged[dev], s, e) * 1e-9 / len(names)
    gs, ge = gaps(*merged[names[0]], lo, hi)
    order = np.argsort(gs - ge, kind="stable")[:top]
    idle = []
    for k in order:
        mid = 0.5 * (gs[k] + ge[k])
        open_ = [s for s in inner if s[1] <= mid <= s[2]]
        name = (min(open_, key=lambda s: s[2] - s[1])[0] if open_
                else WINDOW)
        idle.append([name, float((ge[k] - gs[k]) * 1e-9)])
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": float(np.mean([busy[d] for d in names])),
        "busy_s_by_device": {d: busy[d] for d in names},
        "span_device_s": dict(span_s),
        "span_count": dict(span_n),
        "device_ops": [[n, float(t)] for n, t in ops],
        "idle_gaps": idle,
    }
