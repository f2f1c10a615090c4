"""Reduce a profiler trace by what the program itself writes into it.

``trace.py`` reads the benchmark's own spans (``bench.*``) and each used
device's ``XLA Ops``.  The program adds two things to the same trace, on
the same clock (``repro.spans``):

* host spans at its layer boundaries, named ``glu.<layer>``
  (``glu.factorize``, ``glu.prep``, ``glu.h2d``, ``glu.refine``,
  ``glu.sync``, ``glu.d2h``, ``glu.post``, ...);
* fixed program names, so each device's ``XLA Modules`` line reads
  ``jit_glu_factorize(<fingerprint>)``, ``jit_glu_trisolve(...)``,
  ``jit_glu_residual(...)``, ``jit_glu_correct(...)``; one-op programs
  keep JAX's names (``jit_abs``, ``jit_greater``, ``jit_add``, ...).

:func:`reduce` turns them into device time by program, program launches
per call, and device idle time by the innermost span open in it.  Busy time
comes from ``XLA Ops`` where the trace holds them: a module's interval then
only says which program the ops inside it belong to.  A trace recorded
without ops (a mix's ``trace_detail`` ``"programs"``) is reduced from the
modules alone, aligned by :func:`shifted`; their busy time then includes
the stalls inside each program.

The device planes and the host are not on quite one clock: in a trace
recorded on a TPU v5e a module starts up to 1.5 ms before the host call
that launched it (``PJRT_LoadedExecutable_Execute``).  Attributing device
idle time to host spans needs the two aligned, so :func:`reduce` first
shifts each device's events by :func:`clock_offset`, the least shift that
starts no module before its launch.
"""
from __future__ import annotations

import re
from collections import Counter, defaultdict

import numpy as np

from trace import WINDOW, covered, gaps, union

GLU_PREFIX = "glu."
SPAN_PREFIXES = ("bench.", GLU_PREFIX)
CALL_SPANS = ("bench.step", "bench.sweep")
MODULE_LINE = "XLA Modules"
LAUNCH = "PJRT_LoadedExecutable_Execute"
CORE_LAUNCH = "tpu::System::Execute"     # one per device a program runs on
PROGRAM_PREFIX = "glu_"
OTHER = "other"
_DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)")
_MODULE = re.compile(r"(?:jit_)?(.+?)(?:\(\d+\))?")


def program_name(module: str) -> str:
    """``glu_trisolve`` of the module event ``jit_glu_trisolve(1234)``."""
    return _MODULE.fullmatch(module).group(1)


def clock_offset(launches, modules) -> float:
    """Nanoseconds to add to a device's times so that no module starts
    before its launch: the ``i``-th module in time is the ``i``-th host
    launch (one stream per device)."""
    if len(launches) != len(modules):
        raise ValueError(f"{len(launches)} host launches against "
                         f"{len(modules)} device modules")
    return max([0.0] + [h - m[1] for h, m in zip(sorted(launches), modules)])


def device_offsets(launches, core_launches, modules):
    """``{plane name: clock_offset}``.  A device that ran one module per
    host launch is aligned to those launches.  One that did not (on a mesh,
    a device other than the first runs the sharded programs alone) is
    aligned to the runtime's launches on its own core, ``core_launches``
    keyed by the core id that the device's id names."""
    out = {}
    for plane, evs in modules.items():
        own = launches
        if len(launches) != len(evs):
            core = int(_DEVICE_PLANE.fullmatch(plane).group(1))
            own = core_launches.get(core, [])
        out[plane] = clock_offset(own, evs)
    return out


def read(path: str, device_ids):
    """``(spans, modules, offsets)`` from an ``.xplane.pb`` file.

    ``spans``: ``[(name, start_ns, end_ns)]`` of the host spans named
    ``bench.*`` or ``glu.*``.  ``modules``: ``{plane name: [(program name,
    start_ns, end_ns)]}`` from the ``XLA Modules`` line of the devices
    ``device_ids``, in time order; a used device without that line is an
    error.  ``offsets``: :func:`device_offsets`.
    """
    from jax.profiler import ProfileData

    want = {int(i) for i in device_ids}
    spans, modules, launches, core_launches = [], {}, [], {}
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE_PLANE.fullmatch(plane.name)
        if m and int(m.group(1)) in want:
            lines = {ln.name: ln for ln in plane.lines}
            if MODULE_LINE not in lines:
                raise ValueError(f"{plane.name} has no {MODULE_LINE!r} line; "
                                 f"its lines are {sorted(lines)}")
            modules[plane.name] = sorted(
                ((program_name(ev.name), ev.start_ns,
                  ev.start_ns + ev.duration_ns)
                 for ev in lines[MODULE_LINE].events), key=lambda m: m[1])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == LAUNCH:
                        launches.append(ev.start_ns)
                    elif ev.name == CORE_LAUNCH:
                        core = dict(ev.stats).get("core_id")
                        core_launches.setdefault(core, []).append(ev.start_ns)
                    elif ev.name.startswith(SPAN_PREFIXES):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    missing = want - {int(_DEVICE_PLANE.fullmatch(p).group(1))
                      for p in modules}
    if missing:
        raise ValueError(f"no device plane for device ids {sorted(missing)}")
    return spans, modules, device_offsets(launches, core_launches, modules)


def shifted(modules, offsets):
    """Each device's events ``(name, start_ns, end_ns)`` moved onto the
    host clock by its offset."""
    return {d: [(n, s + offsets[d], e + offsets[d]) for n, s, e in evs]
            for d, evs in modules.items()}


def self_intervals(spans, lo: float, hi: float):
    """``[(name, start, end)]``: ``[lo, hi]`` cut where any span opens or
    closes, each piece owned by the innermost span open over it (``WINDOW``
    where none is).  Spans nest, as one thread's spans do."""
    inner = [s for s in spans if s[0] != WINDOW and s[1] < hi and s[2] > lo]
    cuts = sorted({lo, hi} | {min(max(t, lo), hi)
                              for _, s, e in inner for t in (s, e)})
    out = []
    for a, b in zip(cuts[:-1], cuts[1:]):
        mid = 0.5 * (a + b)
        open_ = [s for s in inner if s[1] <= mid <= s[2]]
        name = (min(open_, key=lambda s: s[2] - s[1])[0] if open_
                else WINDOW)
        out.append((name, a, b))
    return out


def reduce(spans, ops, modules, offsets, top: int = 10) -> dict:
    """Device time by program, launches per call and idle by span.

    ``spans``, ``modules`` and ``offsets`` as :func:`read` gives them
    (``bench.window`` among the spans), ``ops`` the ``devices`` of
    ``trace.read`` (each used device's ``XLA Ops``); each device's ops and
    modules are shifted by its offset first.  Numbers over several devices
    are means over them.  Returns:

    * ``busy_s``: device busy seconds in the window, after the shift;
    * ``program_device_s``: device busy seconds (union of ``XLA Ops``)
      inside the modules of each ``glu_*`` program, by name, and ``other``
      for every other module;
    * ``program_count`` / ``program_count_by_name``: module executions
      that start inside a ``bench.step`` or ``bench.sweep`` span;
    * ``span_idle_s``: device idle seconds in the window by the innermost
      ``bench.*`` or ``glu.*`` span open over them, each instant counted
      once (self intervals), ``bench.window`` where no other span is open;
    * ``idle_gaps``: the ``top`` longest idle gaps of the first device,
      each named by the innermost span open at its midpoint.
    """
    windows = [s for s in spans if s[0] == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    _, lo, hi = windows[0]
    names = sorted(ops)
    if not names or sorted(modules) != names:
        raise ValueError("ops and modules must cover the same devices")
    calls = [s for s in spans if s[0] in CALL_SPANS]
    pieces = self_intervals(spans, lo, hi)
    prog_s = defaultdict(float)
    span_idle = defaultdict(float)
    count = Counter()
    busy_s = 0.0
    for dev in names:
        off = offsets[dev]
        evs = [(s + off, e + off) for _, s, e in ops[dev]
               if e + off > lo and s + off < hi]
        busy = union([e[0] for e in evs], [e[1] for e in evs])
        busy_s += covered(*busy, lo, hi) * 1e-9
        for name, s, e in ((n, s + off, e + off) for n, s, e in modules[dev]):
            if e <= lo or s >= hi:
                continue
            key = name if name.startswith(PROGRAM_PREFIX) else OTHER
            prog_s[key] += covered(*busy, max(s, lo), min(e, hi)) * 1e-9
            if any(c[1] <= s < c[2] for c in calls):
                count[name] += 1
        for name, a, b in pieces:
            span_idle[name] += ((b - a) - covered(*busy, a, b)) * 1e-9
        if dev == names[0]:
            gs, ge = gaps(*busy, lo, hi)
    k = len(names)
    idle = []
    for i in np.argsort(gs - ge, kind="stable")[:top]:
        mid = 0.5 * (gs[i] + ge[i])
        owner = next(n for n, a, b in pieces if a <= mid <= b)
        idle.append([owner, float((ge[i] - gs[i]) * 1e-9)])
    return {
        "busy_s": busy_s / k,
        "program_device_s": {n: v / k for n, v in prog_s.items()},
        "program_count": sum(count.values()) / k,
        "program_count_by_name": {n: c / k for n, c in count.items()},
        "span_idle_s": {n: v / k for n, v in span_idle.items()},
        "idle_gaps": idle,
    }
