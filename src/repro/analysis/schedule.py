"""Executed-schedule verification: the post-bucketing groups.

``verify_plan`` proves the *plan* is a valid schedule; this module proves
the *executor actually built that schedule*.  Bucket fusion, scan stacking,
Pallas (D, R, C) layouts and the dense trailing block all rewrite the plan
arrays into padded device buffers — a bug there (e.g. a bucket merge fusing
a producer level with its consumer) would race while every plan-level check
still passes.

The walk reconstructs the step sequence the device runs: each scan row /
flat level / Pallas level is one step; a step first normalises (time
``2t``: gathers read pre-step state, then the set lands), then applies its
update triples (time ``2t + 1``: l/u gathers read, the scatter-add
writes).  Happens-before is then a pure index computation over the value
array: for every entry, the max update-write time must be strictly below
the min consuming-read time.  This is exact for the executor semantics —
gathers in a step see pre-step state, so a same-time write/read pair IS a
race — and it is schedule-agnostic: merged, reordered, or mis-bucketed
steps are caught without knowing how the schedule was derived.
"""
from __future__ import annotations

import numpy as np

from .report import VerifyReport

__all__ = ["verify_executor", "verify_trisolver"]

_BIG = 1 << 40


def _steps_from_groups(kinds, group_arrays, nnz, rep):
    """Flatten executor groups into per-step (ni, nd, li, ui, di) int64
    tuples; returns (steps, dense_arrays_or_None)."""
    steps = []
    dense = None
    for gi, (kind, arrs) in enumerate(zip(kinds, group_arrays)):
        if kind == "dense":
            if gi != len(kinds) - 1:
                rep.add("EXEC_DENSE_TAIL",
                        f"dense group at position {gi} is not last")
            dense = tuple(np.asarray(a) for a in arrs)
            continue
        if kind in ("scan", "flat"):
            a = [np.asarray(x).astype(np.int64) for x in arrs]
            for k in range(a[0].shape[0]):
                steps.append(tuple(x[k] for x in a))
        elif kind == "pallas":
            ni, nd, li2, ui2, dl, pos = [np.asarray(x).astype(np.int64)
                                         for x in arrs]
            D, R = li2.shape
            C = pos.shape[1]
            if np.any((dl < 0) | (dl > C)):
                rep.add("EXEC_PAD_OOB",
                        f"pallas didx_local outside [0, {C}]", group=gi)
                dl = np.clip(dl, 0, C)
            if np.any((pos < 0) | (pos > nnz)):
                rep.add("EXEC_PAD_OOB",
                        "pallas pos outside [0, nnz]", group=gi)
                pos = np.clip(pos, 0, nnz)
            rr = np.repeat(np.arange(D), R)
            dlf = dl.ravel()
            # local in-column offset -> global value index; the sentinel C
            # and padded pos slots both resolve to the drop index nnz
            di = np.where(dlf < C, pos[rr, np.minimum(dlf, C - 1)], nnz)
            steps.append((ni, nd, li2.ravel(), ui2.ravel(), di))
        else:
            rep.add("EXEC_PAD_OOB", f"unknown group kind {kind!r}", group=gi)
    return steps, dense


def _dense_tail_want(plan, c_star, Np):
    """The ground-truth (Np, Np) position map of the trailing block."""
    n, nnz = plan.n, plan.nnz
    indptr = np.asarray(plan.indptr, dtype=np.int64)
    indices = np.asarray(plan.indices, dtype=np.int64)
    cols_of = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    m = (indices >= c_star) & (cols_of >= c_star)
    want = np.full((Np, Np), nnz, dtype=np.int64)
    want[indices[m] - c_star, cols_of[m] - c_star] = np.flatnonzero(m)
    return want


def verify_executor(fact, *, kinds=None, group_arrays=None) -> VerifyReport:
    """Verify a built :class:`~repro.core.factorize.JaxFactorizer` schedule
    against its plan.  ``kinds``/``group_arrays`` override the factorizer's
    own (the mutation tests feed corrupted schedules through a golden
    factorizer)."""
    plan = fact.plan
    nnz = plan.nnz
    rep = VerifyReport()
    rep.ran("exec_schedule")
    kinds = fact._kinds if kinds is None else tuple(kinds)
    group_arrays = (fact._group_arrays if group_arrays is None
                    else tuple(group_arrays))
    steps, dense = _steps_from_groups(kinds, group_arrays, nnz, rep)

    info = fact.dense_tail_info
    level_cut = plan.num_levels if info is None else info["level_cut"]
    if (dense is None) != (info is None):
        rep.add("EXEC_DENSE_TAIL",
                "dense group and dense_tail_info disagree on existence")
        return rep

    indptr = np.asarray(plan.indptr, dtype=np.int64)
    indices = np.asarray(plan.indices, dtype=np.int64)
    cols_of = np.repeat(np.arange(plan.n, dtype=np.int64), np.diff(indptr))
    diag_idx = np.asarray(plan.diag_idx, dtype=np.int64)

    # slot nnz is the legal drop/fill pad; one extra slot absorbs it so the
    # timing scatters below never special-case padding
    wmax = np.full(nnz + 1, -_BIG, dtype=np.int64)
    rmin = np.full(nnz + 1, _BIG, dtype=np.int64)
    nwrite = np.full(nnz + 1, -1, dtype=np.int64)
    exec_norms, exec_ndiag = [], []
    exec_li, exec_ui, exec_di, exec_t = [], [], [], []

    for t, (ni, nd, li, ui, di) in enumerate(steps):
        for name, a in (("norm_idx", ni), ("norm_diag", nd), ("lidx", li),
                        ("uidx", ui), ("didx", di)):
            if len(a) and (a.min() < 0 or a.max() > nnz):
                rep.add("EXEC_PAD_OOB", f"{name} outside [0, nnz]", step=t)
                return rep
        m = ni != nnz
        if np.any(nd[m] == nnz):
            rep.add("EXEC_PAD_OOB",
                    "norm entry with padded diagonal slot", step=t)
        nmv = ni[m]
        nwrite[nmv] = 2 * t
        np.minimum.at(rmin, nmv, 2 * t)      # the norm's own gather
        np.minimum.at(rmin, nd[m], 2 * t)    # the diagonal read
        exec_norms.append(nmv)
        exec_ndiag.append(nd[m])
        mu = (li != nnz) & (ui != nnz) & (di != nnz)
        mixed = (li != nnz) | (ui != nnz) | (di != nnz)
        if np.any(mixed & ~mu):
            rep.add("EXEC_PAD_OOB", "partially padded update triple", step=t)
        np.minimum.at(rmin, li[mu], 2 * t + 1)
        np.minimum.at(rmin, ui[mu], 2 * t + 1)
        np.maximum.at(wmax, di[mu], 2 * t + 1)
        exec_li.append(li[mu])
        exec_ui.append(ui[mu])
        exec_di.append(di[mu])
        exec_t.append(np.full(int(mu.sum()), t, dtype=np.int64))

    T = len(steps)
    if dense is not None:
        # the dense step gathers every trailing-block entry at its start
        c_star = info["c_star"]
        m = (indices >= c_star) & (cols_of >= c_star)
        np.minimum.at(rmin, np.flatnonzero(m), 2 * T)

    bad = wmax[:nnz] >= rmin[:nnz]
    if np.any(bad):
        e = int(np.flatnonzero(bad)[0])
        rep.add("EXEC_RACE",
                f"entry {e} ({int(indices[e])}, {int(cols_of[e])}) is "
                f"written at time {int(wmax[e])} but read at time "
                f"{int(rmin[e])}",
                entry=e, n_bad=int(bad.sum()))

    if exec_li:
        li = np.concatenate(exec_li)
        ui = np.concatenate(exec_ui)
        di = np.concatenate(exec_di)
        ts = np.concatenate(exec_t)
        bad = nwrite[li] > 2 * ts + 1
        never = nwrite[li] < 0
        if np.any(bad | never):
            i = int(np.flatnonzero(bad | never)[0])
            rep.add("EXEC_SOURCE_ORDER",
                    f"update at step {int(ts[i])} consumes entry "
                    f"{int(li[i])} normalised at time {int(nwrite[li[i]])}",
                    n_bad=int((bad | never).sum()))
    else:
        li = ui = di = np.zeros(0, dtype=np.int64)

    # coverage: the sparse steps must execute EXACTLY the plan's pre-cut
    # normalisations and triples (each once; the dense block owns the rest)
    norm_end = upd_end = 0
    if level_cut > 0 and plan.segments:
        last = plan.segments[min(level_cut, len(plan.segments)) - 1]
        norm_end = last.norm_slice.stop
        upd_end = last.upd_slice.stop
    got_n = (np.sort(np.concatenate(exec_norms)) if exec_norms
             else np.zeros(0, dtype=np.int64))
    want_n = np.sort(np.asarray(plan.norm_idx[:norm_end], dtype=np.int64))
    if not np.array_equal(got_n, want_n):
        rep.add("EXEC_NORM_COVERAGE",
                "executed normalisations differ from the plan's",
                got=len(got_n), want=len(want_n))
    nd_all = (np.concatenate(exec_ndiag) if exec_ndiag
              else np.zeros(0, dtype=np.int64))
    ni_all = (np.concatenate(exec_norms) if exec_norms
              else np.zeros(0, dtype=np.int64))
    if np.any(nd_all != diag_idx[cols_of[ni_all]]):
        rep.add("EXEC_NORM_COVERAGE",
                "executed norm diagonal is not the entry's column diagonal")
    key = li * (nnz + 1) + ui
    order = np.argsort(key, kind="stable")
    pli = np.asarray(plan.lidx[:upd_end], dtype=np.int64)
    pui = np.asarray(plan.uidx[:upd_end], dtype=np.int64)
    pdi = np.asarray(plan.didx[:upd_end], dtype=np.int64)
    pkey = pli * (nnz + 1) + pui
    porder = np.argsort(pkey, kind="stable")
    if not (len(key) == len(pkey)
            and np.array_equal(key[order], pkey[porder])
            and np.array_equal(di[order], pdi[porder])):
        rep.add("EXEC_UPDATE_COVERAGE",
                "executed update triples differ from the plan's",
                got=len(key), want=len(pkey))

    if dense is not None:
        rep.ran("dense_tail")
        c_star, Np = info["c_star"], info["padded"]
        size = info["size"]
        pos, eye = dense[0].astype(np.int64), np.asarray(dense[1])
        levels = np.asarray(plan.levels.levels, dtype=np.int64)
        tail_cols = np.flatnonzero(levels >= level_cut)
        if not np.array_equal(tail_cols, np.arange(c_star, plan.n)):
            rep.add("EXEC_DENSE_TAIL",
                    "columns at levels >= level_cut are not exactly "
                    f"[{c_star}, n)")
        if pos.shape != (Np, Np) or size != plan.n - c_star:
            rep.add("EXEC_DENSE_TAIL", "dense position map has wrong shape")
        else:
            want = _dense_tail_want(plan, c_star, Np)
            if not np.array_equal(pos, want):
                rep.add("EXEC_DENSE_TAIL",
                        "dense position map disagrees with the pattern",
                        n_bad=int((pos != want).sum()))
            want_eye = np.zeros((Np, Np), dtype=eye.dtype)
            ii = np.arange(size, Np)
            want_eye[ii, ii] = 1.0
            if not np.array_equal(eye, want_eye):
                rep.add("EXEC_DENSE_TAIL",
                        "padded-diagonal eye mask is wrong")
    return rep


def _trisolve_steps(groups, width):
    """Flatten stacked (K, P) trisolve groups into per-step tuples."""
    steps = []
    for arrs in groups:
        a = [np.asarray(x).astype(np.int64) for x in arrs]
        if len(a) != width:
            raise ValueError(f"expected {width} arrays per group")
        for k in range(a[0].shape[0]):
            steps.append(tuple(x[k] for x in a))
    return steps


def verify_trisolver(solver, *, fwd_groups=None, bwd_groups=None,
                     tail=None) -> VerifyReport:
    """Verify a built :class:`~repro.core.triangular.JaxTriangularSolver`
    full schedule against its plan (same step-timing discipline as
    :func:`verify_executor`, on the solution vector instead of the value
    array).  With a dense tail the dense step covers the L and U entries
    inside the trailing block and divides its columns; ``tail`` overrides
    the solver's block position map."""
    plan = solver.plan
    n, nnz = plan.n, plan.nnz
    rep = VerifyReport()
    rep.ran("trisolve_schedule")
    if fwd_groups is None or bwd_groups is None:
        fg, bg = solver._full_schedule
        fwd_groups = fg if fwd_groups is None else fwd_groups
        bwd_groups = bg if bwd_groups is None else bwd_groups
    tail = solver._tail if tail is None else tail
    indptr = np.asarray(plan.indptr, dtype=np.int64)
    indices = np.asarray(plan.indices, dtype=np.int64)
    cols_of = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    diag_idx = np.asarray(plan.diag_idx, dtype=np.int64)
    lower = indices > cols_of
    upper = indices < cols_of
    # the dense step owns the block [c*, n) x [c*, n)
    c_star = n
    if (tail is None) != (solver.dense_tail_info is None):
        rep.add("TRISOLVE_DENSE_TAIL",
                "dense-tail step and dense_tail_info disagree on existence")
        return rep
    if tail is not None:
        rep.ran("trisolve_dense_tail")
        c_star = int(solver.dense_tail_info["c_star"])
        size = n - c_star
        # the solver holds the map column by column (transposed)
        pos = np.asarray(tail).astype(np.int64).T
        if (pos.shape != (size, size)
                or not np.array_equal(pos,
                                      _dense_tail_want(plan, c_star, size))):
            rep.add("TRISOLVE_DENSE_TAIL",
                    "dense block position map disagrees with the pattern")
    in_tail = (indices >= c_star) & (cols_of >= c_star)

    # forward sweep: step t reads x[cols] (pre-step) and adds into x[rows]
    fsteps = _trisolve_steps(fwd_groups, 3)
    wmax = np.full(n + 1, -_BIG, dtype=np.int64)
    rmin = np.full(n + 1, _BIG, dtype=np.int64)
    fvs = []
    for t, (rows, cols, vidx) in enumerate(fsteps):
        if np.any((vidx < 0) | (vidx > nnz)) or np.any(
                (rows < 0) | (rows > n)) or np.any((cols < 0) | (cols > n)):
            rep.add("TRISOLVE_FWD_SET", "executed index out of range", step=t)
            return rep
        m = vidx != nnz
        if np.any((rows[m] == n) | (cols[m] == n)):
            rep.add("TRISOLVE_FWD_SET",
                    "valid entry with padded row/col slot", step=t)
        r, c, v = rows[m], cols[m], vidx[m]
        bad = (indices[v] != r) | (cols_of[v] != c) | (r <= c)
        if np.any(bad):
            rep.add("TRISOLVE_FWD_SET",
                    "executed entry disagrees with the L entry it indexes",
                    step=t, n_bad=int(bad.sum()))
        np.minimum.at(rmin, c, t)
        np.maximum.at(wmax, r, t)
        fvs.append(v)
    # the dense step reads the tail of x after every sparse forward step
    rmin[c_star:n] = np.minimum(rmin[c_star:n], len(fsteps))
    fvs.append(np.flatnonzero(lower & in_tail))
    got = np.sort(np.concatenate(fvs))
    if not np.array_equal(got, np.flatnonzero(lower)):
        rep.add("TRISOLVE_FWD_SET",
                "executed forward entries are not exactly L's",
                got=len(got), want=int(lower.sum()))
    bad = wmax[:n] >= rmin[:n]
    if np.any(bad):
        c = int(np.flatnonzero(bad)[0])
        rep.add("TRISOLVE_FWD_RACE",
                f"x[{c}] written at step {int(wmax[c])} but read at step "
                f"{int(rmin[c])}", col=c, n_bad=int(bad.sum()))

    # backward sweep: step t divides its level columns first (sequential in
    # the step body), then its updates read x[cols] / write x[rows].  With
    # a dense tail, time 0 is the dense step (it divides the tail columns)
    # and the level steps follow.
    bsteps = _trisolve_steps(bwd_groups, 5)
    t0 = 0 if tail is None else 1
    t_div = np.full(n + 1, -1, dtype=np.int64)
    n_div = np.zeros(n + 1, dtype=np.int64)
    ents = []
    if tail is not None:
        t_div[c_star:n] = 0
        n_div[c_star:n] = 1
    for t, (lcols, ldiag, rows, cols, vidx) in enumerate(bsteps, start=t0):
        if (np.any((lcols < 0) | (lcols > n))
                or np.any((ldiag < 0) | (ldiag > nnz))
                or np.any((vidx < 0) | (vidx > nnz))
                or np.any((rows < 0) | (rows > n))
                or np.any((cols < 0) | (cols > n))):
            rep.add("TRISOLVE_BWD_SET", "executed index out of range", step=t)
            return rep
        mc = lcols != n
        lc = lcols[mc]
        if np.any(ldiag[mc] != diag_idx[lc]):
            rep.add("TRISOLVE_BWD_SET",
                    "division diagonal is not the column's diag_idx", step=t)
        t_div[lc] = t
        n_div[lc] += 1
        m = vidx != nnz
        r, c, v = rows[m], cols[m], vidx[m]
        bad = (indices[v] != r) | (cols_of[v] != c) | (r >= c)
        if np.any(bad):
            rep.add("TRISOLVE_BWD_SET",
                    "executed entry disagrees with the U entry it indexes",
                    step=t, n_bad=int(bad.sum()))
        ents.append((r, c, v, np.full(len(v), t, dtype=np.int64)))
    if np.any(n_div[:n] != 1):
        rep.add("TRISOLVE_BWD_SET",
                "some column is divided more or less than once",
                n_bad=int((n_div[:n] != 1).sum()))
    if ents:
        r = np.concatenate([e[0] for e in ents])
        c = np.concatenate([e[1] for e in ents])
        v = np.concatenate([e[2] for e in ents])
        ts = np.concatenate([e[3] for e in ents])
    else:
        r = c = v = ts = np.zeros(0, dtype=np.int64)
    # the dense step applies the U entries inside the block itself
    dense_u = np.flatnonzero(upper & in_tail)
    if not np.array_equal(np.sort(np.concatenate([v, dense_u])),
                          np.flatnonzero(upper)):
        rep.add("TRISOLVE_BWD_SET",
                "executed backward entries are not exactly strict U's",
                got=len(v), want=int(upper.sum()))
    bad = (t_div[c] > ts) | (t_div[c] < 0) | (ts >= t_div[r])
    if np.any(bad):
        i = int(np.flatnonzero(bad)[0])
        rep.add("TRISOLVE_BWD_RACE",
                f"update ({int(r[i])}, {int(c[i])}) at step {int(ts[i])} "
                f"races divisions at steps {int(t_div[r[i]])} (row) / "
                f"{int(t_div[c[i]])} (col)",
                n_bad=int(bad.sum()))
    return rep
