"""Numeric LU factorization executors.

* ``factorize_numpy``      — paper Alg. 2 (hybrid right-looking), sequential
                             host oracle, verbatim loop structure.
* ``leftlooking_numpy``    — paper Alg. 1 (G/P left-looking) baseline.
* ``JaxFactorizer``        — the GLU3.0 executor: level-scheduled, three
                             adaptive modes, scan-fused small levels,
                             optional Pallas segmented kernel.

The JaxFactorizer is built once from a :class:`FactorizePlan` and reused for
every refactorization with new numeric values on the same pattern (the
Newton-Raphson inner loop of circuit simulation).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..distributed.collectives import psum_exact
from ..kernels.backend import check_pallas_dtype, resolve_interpret
from ..sparse.layout import pabs, pack_planes, pdiv, pmul, resolve_layout
from ..spans import named
from .executor import resolve_executable_cache
from .plan import (
    MODE_FLAT,
    MODE_PANEL,
    MODE_SEGMENTED,
    FactorizePlan,
    bucketize,
    pow2_pad,
)
from .symbolic import FilledPattern

__all__ = ["factorize_numpy", "leftlooking_numpy", "JaxFactorizer", "split_lu"]


# --------------------------------------------------------------------------
# Host oracles (verbatim paper algorithms)
# --------------------------------------------------------------------------

def _oracle_dtype(vals) -> np.dtype:
    """Working dtype of the host oracles: the input's dtype promoted to at
    least 64-bit precision (float64 for real, complex128 for complex)."""
    return np.result_type(np.asarray(vals).dtype, np.float64)


def factorize_numpy(As: FilledPattern, vals: np.ndarray) -> np.ndarray:
    """Paper Algorithm 2: hybrid column right-looking LU (sequential oracle)."""
    n, indptr, indices = As.n, As.indptr, As.indices
    vals = np.array(vals, dtype=_oracle_dtype(vals), copy=True)
    for j in range(n):
        s, e = int(indptr[j]), int(indptr[j + 1])
        rows = indices[s:e]
        dp = s + int(np.searchsorted(rows, j))
        diag = vals[dp]
        # compute column j of L
        vals[dp + 1 : e] /= diag
        # update the submatrix: for k > j with As(j, k) != 0
        lrows = rows[dp + 1 - s :]
        lvals = vals[dp + 1 : e]
        if len(lrows) == 0:
            continue
        for k in range(j + 1, n):
            ks, ke = int(indptr[k]), int(indptr[k + 1])
            p = ks + int(np.searchsorted(indices[ks:ke], j))
            if p < ke and indices[p] == j:
                ujk = vals[p]
                pos = ks + np.searchsorted(indices[ks:ke], lrows)
                vals[pos] -= lvals * ujk
    return vals


def _row_major_view(As: FilledPattern):
    from ..sparse.csc import csc_transpose_pattern

    return csc_transpose_pattern(As.n, As.indptr, As.indices)


def factorize_numpy_fast(As: FilledPattern, vals: np.ndarray) -> np.ndarray:
    """Same math as :func:`factorize_numpy`, using a CSR view to find the
    subcolumns of j directly (used by larger tests/benchmarks)."""
    n, indptr, indices = As.n, As.indptr, As.indices
    indptr_t, indices_t, pos_t = _row_major_view(As)
    vals = np.array(vals, dtype=_oracle_dtype(vals), copy=True)
    for j in range(n):
        s, e = int(indptr[j]), int(indptr[j + 1])
        rows = indices[s:e]
        dp = s + int(np.searchsorted(rows, j))
        vals[dp + 1 : e] /= vals[dp]
        lrows = rows[dp + 1 - s :]
        lvals = vals[dp + 1 : e]
        if len(lrows) == 0:
            continue
        ts, te = int(indptr_t[j]), int(indptr_t[j + 1])
        krange = indices_t[ts:te]
        kpos = pos_t[ts:te]
        right = krange > j
        for k, up in zip(krange[right], kpos[right]):
            ks, ke = int(indptr[k]), int(indptr[k + 1])
            pos = ks + np.searchsorted(indices[ks:ke], lrows)
            vals[pos] -= lvals * vals[up]
    return vals


def leftlooking_numpy(As: FilledPattern, vals: np.ndarray) -> np.ndarray:
    """Paper Algorithm 1: Gilbert-Peierls left-looking LU (baseline)."""
    n, indptr, indices = As.n, As.indptr, As.indices
    vals = np.array(vals, dtype=_oracle_dtype(vals), copy=True)
    for j in range(n):
        s, e = int(indptr[j]), int(indptr[j + 1])
        rows = indices[s:e]
        dp = s + int(np.searchsorted(rows, j))
        # triangular solve: for k < j with As(k, j) != 0 ascending
        for p in range(s, dp):
            k = int(indices[p])
            akj = vals[p]
            ks, ke = int(indptr[k]), int(indptr[k + 1])
            kdp = ks + int(np.searchsorted(indices[ks:ke], k))
            lrows = indices[kdp + 1 : ke]
            if len(lrows) == 0:
                continue
            pos = s + np.searchsorted(rows, lrows)
            vals[pos] -= vals[kdp + 1 : ke] * akj
        vals[dp + 1 : e] /= vals[dp]
    return vals


def split_lu(As: FilledPattern, vals: np.ndarray):
    """Split factorized values into scipy L (unit diag) and U matrices."""
    import scipy.sparse as sp

    n, indptr, indices = As.n, As.indptr, As.indices
    vals = np.asarray(vals)
    cols = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    lower = indices > cols
    upper = ~lower
    L = sp.coo_matrix((vals[lower], (indices[lower], cols[lower])), shape=(n, n)).tocsc()
    L = L + sp.eye(n, format="csc")
    U = sp.coo_matrix((vals[upper], (indices[upper], cols[upper])), shape=(n, n)).tocsc()
    return L, U


# --------------------------------------------------------------------------
# JAX executor
# --------------------------------------------------------------------------

def _pad_to(x: np.ndarray, size: int, fill: int) -> np.ndarray:
    out = np.full(size, fill, dtype=np.int32)
    out[: len(x)] = x
    return out


_pow2 = pow2_pad


def _level_step_body(vals, norm_idx, norm_diag, lidx, uidx, didx):
    lv = vals.at[norm_idx].get(mode="fill", fill_value=0.0)
    dv = vals.at[norm_diag].get(mode="fill", fill_value=1.0)
    vals = vals.at[norm_idx].set(lv / dv, mode="drop")
    l = vals.at[lidx].get(mode="fill", fill_value=0.0)
    u = vals.at[uidx].get(mode="fill", fill_value=0.0)
    return vals.at[didx].add(-l * u, mode="drop")


def _scan_steps_body(vals, norm_idx, norm_diag, lidx, uidx, didx):
    """Run a stack of same-shape levels sequentially inside one dispatch."""

    def body(v, xs):
        return _level_step_body(v, *xs), None

    vals, _ = jax.lax.scan(body, vals, (norm_idx, norm_diag, lidx, uidx, didx))
    return vals


def _level_step_robust_body(vals, lev_diag, tau, norm_idx, norm_diag,
                            lidx, uidx, didx):
    """Level step with static pivot perturbation: diagonals of the level's
    columns are final once all earlier levels ran, so any ``|d| < tau`` is
    bumped right before the divisions that would otherwise produce
    inf/NaN (one bump rule for every executor path: _perturb_diags_body)."""
    from ..kernels.ops import _perturb_diags_body

    vals, n_bumped = _perturb_diags_body(vals, lev_diag, tau)
    return _level_step_body(vals, norm_idx, norm_diag, lidx, uidx, didx), n_bumped


def _scan_steps_robust_body(vals, lev_diag, tau, norm_idx, norm_diag,
                            lidx, uidx, didx):
    def body(v, xs):
        v, c = _level_step_robust_body(v, xs[0], tau, *xs[1:])
        return v, c

    vals, counts = jax.lax.scan(
        body, vals, (lev_diag, norm_idx, norm_diag, lidx, uidx, didx))
    return vals, jnp.sum(counts)


_level_step = partial(jax.jit, donate_argnums=(0,))(_level_step_body)
_scan_steps = partial(jax.jit, donate_argnums=(0,))(_scan_steps_body)
_level_step_robust = partial(jax.jit, donate_argnums=(0,))(_level_step_robust_body)
_scan_steps_robust = partial(jax.jit, donate_argnums=(0,))(_scan_steps_robust_body)

# Batched twins: vals carries a leading batch axis (B, nnz); the per-level
# index arrays are shared across the batch, so each group is still ONE
# device dispatch for the whole batch.  The un-jitted ``*_body`` vmaps are
# reused inside the whole-schedule fused program.
_IN_AXES = (0, None, None, None, None, None)
_level_step_batched_body = jax.vmap(_level_step_body, in_axes=_IN_AXES)
_scan_steps_batched_body = jax.vmap(_scan_steps_body, in_axes=_IN_AXES)
_level_step_batched = partial(jax.jit, donate_argnums=(0,))(
    _level_step_batched_body)
_scan_steps_batched = partial(jax.jit, donate_argnums=(0,))(
    _scan_steps_batched_body)
# robust twins additionally map the per-matrix perturbation threshold tau
_IN_AXES_ROBUST = (0, None, 0, None, None, None, None, None)
_level_step_robust_batched_body = jax.vmap(_level_step_robust_body,
                                           in_axes=_IN_AXES_ROBUST)
_scan_steps_robust_batched_body = jax.vmap(_scan_steps_robust_body,
                                           in_axes=_IN_AXES_ROBUST)
_level_step_robust_batched = partial(jax.jit, donate_argnums=(0,))(
    _level_step_robust_batched_body)
_scan_steps_robust_batched = partial(jax.jit, donate_argnums=(0,))(
    _scan_steps_robust_batched_body)


# Planar complex twins (layout="planar"): ``vals`` carries split re/im
# planes, (nnz, 2) single / (B, nnz, 2) batched.  All index machinery is
# identical — gathers/scatters on a (nnz, 2) array index ROWS, so the same
# plan arrays and pad-index-== nnz drop/fill semantics apply — only the
# value arithmetic changes: complex MAC = 4 real MACs + sign (``pmul``),
# normalisation divides by conj(d)/|d|^2 (``pdiv``).

def _level_step_planar_body(vals, norm_idx, norm_diag, lidx, uidx, didx):
    lv = vals.at[norm_idx].get(mode="fill", fill_value=0.0)
    dv = vals.at[norm_diag].get(mode="fill", fill_value=1.0)
    vals = vals.at[norm_idx].set(pdiv(lv, dv), mode="drop")
    l = vals.at[lidx].get(mode="fill", fill_value=0.0)
    u = vals.at[uidx].get(mode="fill", fill_value=0.0)
    return vals.at[didx].add(-pmul(l, u), mode="drop")


def _scan_steps_planar_body(vals, norm_idx, norm_diag, lidx, uidx, didx):
    def body(v, xs):
        return _level_step_planar_body(v, *xs), None

    vals, _ = jax.lax.scan(body, vals,
                           (norm_idx, norm_diag, lidx, uidx, didx))
    return vals


def _level_step_robust_planar_body(vals, lev_diag, tau, norm_idx, norm_diag,
                                   lidx, uidx, didx):
    from ..kernels.ops import _perturb_diags_planar_body

    vals, n_bumped = _perturb_diags_planar_body(vals, lev_diag, tau)
    return (_level_step_planar_body(vals, norm_idx, norm_diag,
                                    lidx, uidx, didx), n_bumped)


def _scan_steps_robust_planar_body(vals, lev_diag, tau, norm_idx, norm_diag,
                                   lidx, uidx, didx):
    def body(v, xs):
        v, c = _level_step_robust_planar_body(v, xs[0], tau, *xs[1:])
        return v, c

    vals, counts = jax.lax.scan(
        body, vals, (lev_diag, norm_idx, norm_diag, lidx, uidx, didx))
    return vals, jnp.sum(counts)


_level_step_planar = partial(jax.jit, donate_argnums=(0,))(
    _level_step_planar_body)
_scan_steps_planar = partial(jax.jit, donate_argnums=(0,))(
    _scan_steps_planar_body)
_level_step_robust_planar = partial(jax.jit, donate_argnums=(0,))(
    _level_step_robust_planar_body)
_scan_steps_robust_planar = partial(jax.jit, donate_argnums=(0,))(
    _scan_steps_robust_planar_body)

# the batch axis maps over the leading axis of (B, nnz, 2) vals; the same
# in_axes as the native twins apply
_level_step_planar_batched_body = jax.vmap(_level_step_planar_body,
                                           in_axes=_IN_AXES)
_scan_steps_planar_batched_body = jax.vmap(_scan_steps_planar_body,
                                           in_axes=_IN_AXES)
_level_step_planar_batched = partial(jax.jit, donate_argnums=(0,))(
    _level_step_planar_batched_body)
_scan_steps_planar_batched = partial(jax.jit, donate_argnums=(0,))(
    _scan_steps_planar_batched_body)
_level_step_robust_planar_batched_body = jax.vmap(
    _level_step_robust_planar_body, in_axes=_IN_AXES_ROBUST)
_scan_steps_robust_planar_batched_body = jax.vmap(
    _scan_steps_robust_planar_body, in_axes=_IN_AXES_ROBUST)
_level_step_robust_planar_batched = partial(jax.jit, donate_argnums=(0,))(
    _level_step_robust_planar_batched_body)
_scan_steps_robust_planar_batched = partial(jax.jit, donate_argnums=(0,))(
    _scan_steps_robust_planar_batched_body)


def _round_up(x: int, m: int) -> int:
    return ((max(x, 1) + m - 1) // m) * m


def _build_pallas_layout(plan: FactorizePlan, seg, pad_key: int):
    """Host-side (D, R, C) segmented layout for one level (see kernels/ops)."""
    us = seg.upd_slice
    dst = plan.dst_col[us]
    li, ui, di = plan.lidx[us], plan.uidx[us], plan.didx[us]
    uniq, starts = np.unique(dst, return_index=True)
    starts = np.append(starts, len(dst))
    counts = np.diff(starts)
    D = len(uniq)
    R = _round_up(int(counts.max()) if D else 1, 256)
    col_start = plan.indptr[uniq].astype(np.int64)
    col_len = (plan.indptr[uniq + 1] - plan.indptr[uniq]).astype(np.int64)
    Cmax = int(col_len.max()) if D else 1
    C = _round_up(Cmax, 128) if Cmax <= 512 else _round_up(Cmax, 512)

    lidx2d = np.full((D, R), pad_key, dtype=np.int32)
    uidx2d = np.full((D, R), pad_key, dtype=np.int32)
    didx_local = np.full((D, R), C, dtype=np.int32)
    for r in range(D):
        s, e = starts[r], starts[r + 1]
        m = e - s
        lidx2d[r, :m] = li[s:e]
        uidx2d[r, :m] = ui[s:e]
        didx_local[r, :m] = di[s:e] - col_start[r]
    pos = col_start[:, None] + np.arange(C)[None, :]
    pos = np.where(np.arange(C)[None, :] < col_len[:, None], pos, pad_key)
    ns = seg.norm_slice
    pn = _pow2(seg.n_norm)
    return (
        jnp.asarray(_pad_to(plan.norm_idx[ns], pn, pad_key)),
        jnp.asarray(_pad_to(plan.norm_diag[ns], pn, pad_key)),
        jnp.asarray(lidx2d),
        jnp.asarray(uidx2d),
        jnp.asarray(didx_local),
        jnp.asarray(pos.astype(np.int32)),
    )


def _find_dense_tail(plan: FactorizePlan, min_size: int = 64,
                     max_size: int = 1024, density: float = 0.25):
    """Beyond-paper switch-to-dense: find a level suffix whose columns form a
    trailing [c*, n) block dense enough to finish with one blocked dense LU
    (the MXU replaces hundreds of tiny type-C levels).  Returns
    (level_cut, c_star) or None.

    Correctness: dependencies only point forward, updates from column j only
    write rows in L(j) (all >= c* when j >= c*), and the filled pattern is
    elimination-closed — so the dense block factorization is exact and
    entries outside the pattern stay identically zero (see DESIGN.md).
    """
    n = plan.n
    nlev = plan.num_levels
    if nlev < 4:
        return None
    lo, hi = max(n - max_size, 1), n - min_size
    if hi < lo:
        return None
    levels = plan.levels.levels.astype(np.int64)
    # clean column partition: columns [0,c) must all be in levels < l* and
    # columns [c,n) all in levels >= l* — otherwise a tail column would be
    # factorized twice (once sparsely, once densely)
    pmax = np.concatenate([[-1], np.maximum.accumulate(levels)])   # pmax[c]
    smin = np.minimum.accumulate(levels[::-1])[::-1]               # smin[c]
    cols = np.repeat(np.arange(n, dtype=np.int64), np.diff(plan.indptr))
    # entries inside the trailing [c, n) block are exactly those with
    # min(row, col) >= c: one histogram + suffix-sum covers every candidate
    m = np.minimum(cols, plan.indices.astype(np.int64))
    suffix = np.cumsum(np.bincount(m, minlength=n + 1)[::-1])[::-1]
    c = np.arange(lo, hi + 1, dtype=np.int64)
    size = n - c
    ok = (pmax[c] < smin[c]) & (suffix[c] / (size * size) >= density)
    idx = np.flatnonzero(ok)
    if not idx.size:
        return None
    c_star = int(c[idx[0]])    # smallest cut = largest qualifying tail
    return int(smin[c_star]), int(c_star)


def dense_tail_positions(plan: FactorizePlan, c_star: int,
                         padded: int) -> np.ndarray:
    """(padded, padded) value index of each entry of the trailing block
    [c*, n) x [c*, n); ``nnz`` (the fill/drop pad) where the pattern has
    none."""
    indptr = np.asarray(plan.indptr, dtype=np.int64)
    indices = np.asarray(plan.indices, dtype=np.int64)
    cols = np.repeat(np.arange(plan.n, dtype=np.int64), np.diff(indptr))
    m = (indices >= c_star) & (cols >= c_star)
    pos = np.full((padded, padded), plan.nnz, dtype=np.int32)
    pos[indices[m] - c_star, cols[m] - c_star] = np.flatnonzero(m)
    return pos


def _build_dense_tail(plan: FactorizePlan, c_star: int):
    """(positions (Np,Np) into vals, eye mask, Np) for the trailing block."""
    size = plan.n - c_star
    Np = ((size + 127) // 128) * 128
    pos = dense_tail_positions(plan, c_star, Np)
    eye = np.zeros((Np, Np), dtype=np.float32)
    ii = np.arange(size, Np)
    eye[ii, ii] = 1.0
    return jnp.asarray(pos), jnp.asarray(eye), Np


def _dense_tail_step_body(vals, pos, eye, *, interpret=None, use_pallas=False):
    dense = vals.at[pos].get(mode="fill", fill_value=0.0)
    dense = dense + eye.astype(vals.dtype)
    if use_pallas:
        from ..kernels.dense_lu import dense_lu

        dense = dense_lu(dense, interpret=interpret)
    else:
        from ..kernels.ref import dense_lu_ref

        dense = dense_lu_ref(dense)
    return vals.at[pos].set(dense, mode="drop")


_dense_tail_step = partial(
    jax.jit, donate_argnums=(0,), static_argnames=("interpret", "use_pallas"))(
    _dense_tail_step_body)


def _dense_tail_step_batched_body(vals, pos, eye):
    """Batched trailing block: gather (B, Np, Np), vmapped blocked LU,
    scatter back.  Always uses the XLA reference LU — the Pallas dense
    kernel stays a per-matrix dispatch on the unbatched path."""
    from ..kernels.ref import dense_lu_ref

    dense = vals.at[:, pos].get(mode="fill", fill_value=0.0)
    dense = dense + eye.astype(vals.dtype)[None]
    dense = jax.vmap(dense_lu_ref)(dense)
    return vals.at[:, pos].set(dense, mode="drop")


_dense_tail_step_batched = partial(jax.jit, donate_argnums=(0,))(
    _dense_tail_step_batched_body)


def _dense_tail_step_planar_body(vals, pos, eye, *, interpret=None,
                                 use_pallas=False):
    """Planar trailing block: gather (Np, Np, 2), factor the (2, Np, Np)
    plane pair (Pallas planar kernel or its XLA twin), scatter back.  The
    eye mask pads only the REAL plane — padded diagonal slots become 1+0j,
    exactly as on the native path."""
    dense = vals.at[pos].get(mode="fill", fill_value=0.0)
    dense = jnp.moveaxis(dense, -1, 0)
    dense = dense.at[0].add(eye.astype(dense.dtype))
    if use_pallas:
        from ..kernels.dense_lu import dense_lu_planar

        dense = dense_lu_planar(dense, interpret=interpret)
    else:
        from ..kernels.ref import dense_lu_planar_ref

        dense = dense_lu_planar_ref(dense)
    return vals.at[pos].set(jnp.moveaxis(dense, 0, -1), mode="drop")


_dense_tail_step_planar = partial(
    jax.jit, donate_argnums=(0,), static_argnames=("interpret", "use_pallas"))(
    _dense_tail_step_planar_body)


def _dense_tail_step_planar_batched_body(vals, pos, eye):
    from ..kernels.ref import dense_lu_planar_ref

    dense = vals.at[:, pos].get(mode="fill", fill_value=0.0)  # (B, Np, Np, 2)
    dense = jnp.moveaxis(dense, -1, 1)                        # (B, 2, Np, Np)
    dense = dense.at[:, 0].add(eye.astype(dense.dtype)[None])
    dense = jax.vmap(dense_lu_planar_ref)(dense)
    return vals.at[:, pos].set(jnp.moveaxis(dense, 1, -1), mode="drop")


_dense_tail_step_planar_batched = partial(jax.jit, donate_argnums=(0,))(
    _dense_tail_step_planar_batched_body)


@dataclasses.dataclass
class _Group:
    """One executor step: a scan-fused run, a single flat level, a
    Pallas-segmented level, or the dense trailing block."""

    kind: str      # "scan" | "flat" | "pallas" | "dense"
    arrays: tuple
    mode: str      # source level mode(s); "mixed" when a bucketed run fused
                   # levels of different modes (they execute identically on
                   # the non-Pallas path)
    # diag value indices of the columns this step factorizes ((K, Pc) for
    # scan groups, (Pc,) otherwise; padded with nnz) — the static-pivot
    # perturbation targets
    diag: object = None
    n_levels: int = 1


# --------------------------------------------------------------------------
# Whole-schedule fused program
# --------------------------------------------------------------------------
#
# The per-group dispatch loop (the ``jit_schedule=False`` path below) issues
# one jitted call per group — hundreds of host->device round-trips on long,
# narrow circuit schedules, exactly the launch overhead GLU3.0 amortizes
# with CUDA streams / pipelining.  ``_build_factorize_runner`` compiles the
# ENTIRE schedule (A-value scatter, every scan/flat/pallas/dense group, the
# static-pivot guard) into one jitted program, so a (re)factorization is a
# single device dispatch.  Runners are cached process-wide by plan digest +
# executor config (see core/executor.py).

def _schedule_step_bodies(planar: bool, batched: bool) -> dict:
    """The un-jitted step-body set for one (layout, batched) combination —
    the native and planar paths trace the same schedule through different
    arithmetic."""
    from ..kernels import ops as kops

    if planar:
        return dict(
            scan=(_scan_steps_planar_batched_body if batched
                  else _scan_steps_planar_body),
            scan_robust=(_scan_steps_robust_planar_batched_body if batched
                         else _scan_steps_robust_planar_body),
            flat=(_level_step_planar_batched_body if batched
                  else _level_step_planar_body),
            flat_robust=(_level_step_robust_planar_batched_body if batched
                         else _level_step_robust_planar_body),
            pallas=(kops.level_update_planar_batched_body if batched
                    else kops.level_update_planar_body),
            perturb=kops._perturb_diags_planar_body,
            dense=(_dense_tail_step_planar_batched_body if batched
                   else _dense_tail_step_planar_body),
        )
    return dict(
        scan=(_scan_steps_batched_body if batched else _scan_steps_body),
        scan_robust=(_scan_steps_robust_batched_body if batched
                     else _scan_steps_robust_body),
        flat=(_level_step_batched_body if batched else _level_step_body),
        flat_robust=(_level_step_robust_batched_body if batched
                     else _level_step_robust_body),
        pallas=(kops.level_update_batched_body if batched
                else kops.level_update_body),
        perturb=kops._perturb_diags_body,
        dense=(_dense_tail_step_batched_body if batched
               else _dense_tail_step_body),
    )


def _apply_schedule_groups(vals, groups, diags, tau, *, kinds, robust,
                           batched, interpret, use_pallas, planar=False):
    """Trace every group of the schedule in order; returns (vals, counts)
    where ``counts`` collects the per-group static-pivot bump counts
    (empty unless ``robust``)."""
    bodies = _schedule_step_bodies(planar, batched)

    def perturb(vals, diag, tau):
        if batched:
            return jax.vmap(bodies["perturb"],
                            in_axes=(0, None, 0))(vals, diag, tau)
        return bodies["perturb"](vals, diag, tau)

    counts = []
    for kind, arrs, diag in zip(kinds, groups, diags):
        if kind == "scan":
            if robust:
                vals, c = bodies["scan_robust"](vals, diag, tau, *arrs)
                counts.append(c)
            else:
                vals = bodies["scan"](vals, *arrs)
        elif kind == "pallas":
            if robust:
                vals, c = perturb(vals, diag, tau)
                counts.append(c)
            vals = bodies["pallas"](vals, *arrs, interpret=interpret)
        elif kind == "dense":
            if robust:
                vals, c = perturb(vals, diag, tau)
                counts.append(c)
            if batched:
                vals = bodies["dense"](vals, *arrs)
            else:
                vals = bodies["dense"](vals, *arrs, interpret=interpret,
                                       use_pallas=use_pallas)
        else:  # flat
            flat = tuple(a[0] for a in arrs)
            if robust:
                vals, c = bodies["flat_robust"](vals, diag, tau, *flat)
                counts.append(c)
            else:
                vals = bodies["flat"](vals, *flat)
    return vals, counts


def _build_factorize_runner(kinds, *, entry, batched, robust, interpret,
                            use_pallas, nnz, dtype, planar=False, shard=None):
    """One jitted program for the whole schedule.

    ``entry="scatter"`` takes A values (nnz_A,) / (B, nnz_A) plus the
    scatter map and builds the filled value array inside the program (no
    separate un-donated scatter dispatch); ``entry="filled"`` takes an
    already-filled (and donated) value array.  Returns ``vals`` — plus
    ``(a_max, n_perturbed)`` when the static-pivot guard is on.

    With ``planar`` the program runs on split re/im planes: a "scatter"
    entry takes logical (native complex) A values and packs them INSIDE the
    jitted program; a "filled" entry takes an already-planar (.., nnz, 2)
    array.  ``dtype`` is then the real plane/storage dtype.

    With ``shard`` (a :class:`~repro.distributed.ScenarioSharding`; batched
    entries only) the whole program is wrapped in ``shard_map``: the batch
    axis splits along the scenario mesh axes while the plan metadata
    (scatter map, group index arrays, diag targets) is replicated, so each
    shard runs the full fused schedule — ONE dispatch — on its B/n_shards
    slice.  Every per-matrix reduction (``a_max``, perturbation counts)
    stays within its own batch row, so the sharded result is bit-identical
    to the single-device batched program.  The robust path additionally
    returns the perturbation count summed across the whole (global) batch
    via an exact psum, so ladder diagnostics see one aggregate without a
    second dispatch.
    """

    def run(a, a_scatter, groups, diags, eps):
        if entry == "scatter":
            if planar:
                a = pack_planes(a, dtype)
            shape = ((a.shape[0], nnz) if batched else (nnz,))
            if planar:
                shape = shape + (2,)
            vals = jnp.zeros(shape, dtype=dtype)
            if batched:
                vals = vals.at[:, a_scatter].set(a)
            else:
                vals = vals.at[a_scatter].set(a)
        else:
            vals = a
        if robust:
            mag = pabs(vals) if planar else jnp.abs(vals)
            a_max = jnp.max(mag, axis=1) if batched else jnp.max(mag)
            tau = eps * a_max
        else:
            a_max = tau = None
        vals, counts = _apply_schedule_groups(
            vals, groups, diags, tau, kinds=kinds, robust=robust,
            batched=batched, interpret=interpret, use_pallas=use_pallas,
            planar=planar)
        if robust:
            if counts:
                n_pert = sum(counts)
            elif batched:
                n_pert = jnp.zeros(vals.shape[0], dtype=jnp.int32)
            else:
                n_pert = jnp.asarray(0, dtype=jnp.int32)
            if shard is not None:
                n_pert_global = psum_exact(jnp.sum(n_pert), shard.axis_names)
                return vals, a_max, n_pert, n_pert_global
            return vals, a_max, n_pert
        return vals

    donate = (0,) if entry == "filled" else ()
    if shard is None:
        return jax.jit(named("glu_factorize", run), donate_argnums=donate)
    if not batched:
        raise ValueError("scenario sharding requires a batched entry")
    bspec = shard.spec
    # batch arg sharded along the scenario axes; plan metadata (scatter map,
    # group arrays, diag targets, eps) replicated — P() is a pytree-prefix
    # spec so it covers the nested group tuples (and None leaves) wholesale.
    in_specs = (bspec, P(), P(), P(), P())
    if robust:
        # per-matrix outputs stay batch-sharded; the psum'd global count is
        # replicated (identical on every shard, so check_vma=False is safe).
        out_specs = (bspec, bspec, bspec, P())
    else:
        out_specs = bspec
    mapped = jax.shard_map(run, mesh=shard.mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    return jax.jit(named("glu_factorize", mapped), donate_argnums=donate)


class JaxFactorizer:
    """Level-scheduled GLU3.0 numeric factorization, compiled once per plan.

    Parameters
    ----------
    plan: FactorizePlan
    dtype: value dtype (paper uses float32; float64 also supported — TPU
        scatter-add is deterministic so there is no atomics restriction)
    fuse_levels: scan-fuse runs of levels with equal padded shapes (the TPU
        analogue of reducing per-level kernel-launch overhead / CUDA streams)
    fuse_buckets: quantize level shapes to a small geometric ladder chosen
        from the plan's level-shape histogram before fusing, so long runs of
        NEAR-equal narrow levels still collapse into one ``lax.scan`` group
        (pad-index-``== nnz`` drop semantics make the over-padding bit-safe).
        Implies nothing when ``fuse_levels=False``.
    bucket_waste: per-axis over-padding bound for the bucket ladder — a
        level is never padded past ``bucket_waste ×`` its own pow2 pad
    jit_schedule: compile the whole schedule (scatter + every group) into
        ONE jitted program per plan digest so a factorization is a single
        device dispatch; ``False`` restores the per-group dispatch loop
    executable_cache: where whole-schedule programs are cached —
        ``"default"`` (process-wide cache, shared across GLU rebuilds on the
        same plan), an :class:`~repro.core.executor.ExecutableCache`, or
        ``None`` (private per-instance cache)
    use_pallas: route SEGMENTED/PANEL levels through the Pallas kernel
        (compiled on a TPU, where it needs float32 storage; interpret mode
        elsewhere)
    interpret: run the Pallas kernels in interpret mode.  ``None`` (the
        default) resolves from the platform: interpreted exactly when the
        backend is not a TPU.  ``True`` on a TPU raises.
    dense_tail: switch-to-dense (on by default): when a trailing column
        block is dense enough, the hundreds of tiny levels covering it are
        replaced by ONE blocked dense-LU group inside the same fused
        program — on fill-heavy ordered circuit matrices this converts the
        dominant share of scatter-add update triples into matmuls (a >3x
        end-to-end factorization win on the benchmark suite).  A no-op on
        patterns with no qualifying tail; disable for strictly
        sparse-schedule execution.
    layout: value-storage layout — ``"native"`` (default) stores values in
        their own dtype; ``"planar"`` stores complex values as split re/im
        planes ``(..., 2)`` of the matching real dtype so every kernel —
        including the Pallas SEGMENTED/PANEL and dense-tail kernels, which
        take no complex operands — computes the complex MAC on real
        operands; ``"auto"`` picks planar for complex dtypes.  Planar
        factors come back as ``(nnz, 2)`` / ``(B, nnz, 2)`` real arrays
        (``repro.sparse.unpack_planes`` recovers native complex).
    shard: optional :class:`~repro.distributed.ScenarioSharding` — batched
        factorizations shard the batch axis across the mesh (plan metadata
        replicated, one fused dispatch per shard); unbatched calls and
        batches not divisible by the shard count run the unsharded
        executable.  The ExecutableCache key carries the mesh descriptor so
        sharded and unsharded runners never collide.
    """

    def __init__(
        self,
        plan: FactorizePlan,
        dtype=jnp.float32,
        fuse_levels: bool = True,
        fuse_buckets: bool = True,
        bucket_waste: float = 4.0,
        jit_schedule: bool = True,
        executable_cache="default",
        use_pallas: bool = False,
        mode_override: Optional[str] = None,
        disable_modes: tuple = (),
        interpret: Optional[bool] = None,
        dense_tail: bool = True,
        dense_tail_density: float = 0.25,
        static_pivot: Optional[float] = None,
        layout: str = "native",
        shard=None,
    ):
        self.plan = plan
        # Scenario sharding: batched entry points split the batch axis over
        # the shard's mesh (shard_map around the fused runner); unbatched
        # calls and non-divisible batches fall back to the unsharded
        # executable.  A 1-shard resolution degenerates to None.
        self.shard = shard if (shard is not None and shard.n_shards > 1) \
            else None
        self.dtype = dtype
        self.layout = resolve_layout(layout, dtype)
        self.storage_dtype = self.layout.storage_dtype
        # Why Pallas is (partially) off is surfaced instead of silently
        # downgraded: ``pallas_disabled_reason`` is None iff SEGMENTED/PANEL
        # levels and the dense tail run as compiled Pallas kernels.
        reason = None
        if not use_pallas:
            reason = "use_pallas=False"
        elif (np.issubdtype(np.dtype(dtype), np.complexfloating)
              and not self.layout.planar):
            # Pallas TPU kernels take no complex operands: with native
            # complex storage the SEGMENTED/PANEL levels (and the dense
            # tail) route through the equivalent flat XLA path.  Planar
            # re/im storage (layout="planar" or "auto") keeps them on the
            # Pallas path.
            use_pallas = False
            reason = ("complex dtype with layout='native' "
                      "(pass layout='planar' to keep Pallas kernels)")
        elif (mode_override is not None
              and mode_override not in (MODE_SEGMENTED, MODE_PANEL)):
            reason = (f"mode_override={mode_override!r} routes every level "
                      "off the Pallas path")
        elif MODE_SEGMENTED in disable_modes and MODE_PANEL in disable_modes:
            reason = "disable_modes removes every Pallas-eligible mode"
        if use_pallas:
            check_pallas_dtype(self.storage_dtype)
        self.pallas_disabled_reason = reason
        self.use_pallas = use_pallas
        # interpret mode exactly off the TPU; an explicit True on a TPU raises
        self.interpret = resolve_interpret(interpret)
        self._a_scatter = jnp.asarray(plan.a_scatter, dtype=jnp.int32)
        self.nnz = plan.nnz
        # static pivot perturbation: |diag| < static_pivot * max|A| is bumped
        # instead of dividing toward inf/NaN (None disables; the fast path
        # then runs the exact same jitted steps as before).  Granularity is
        # per level: each level's diagonals are final when its step starts.
        # The dense trailing block is the one exception — only its
        # pre-elimination diagonals are guarded; a pivot that turns tiny
        # *during* the in-tail dense elimination is not re-checked (combine
        # static_pivot with dense_tail=False if that guarantee matters).
        self.static_pivot = static_pivot
        self._diag_idx = jnp.asarray(plan.diag_idx, dtype=jnp.int32)
        self.last_a_max = None
        self.last_n_perturbed = None
        # global (cross-shard) perturbation count of the most recent sharded
        # robust factorization; None on unsharded paths
        self.last_n_perturbed_global = None

        pad_key = plan.nnz  # padding index == nnz -> drop/fill semantics
        self.dense_tail_info = None
        level_cut = plan.num_levels
        if dense_tail:
            found = _find_dense_tail(plan, density=dense_tail_density)
            if found is not None:
                level_cut, c_star = found
                pos, eye, Np = _build_dense_tail(plan, c_star)
                self.dense_tail_info = dict(level_cut=level_cut, c_star=c_star,
                                            size=plan.n - c_star, padded=Np)
                self._dense_tail = (pos, eye)

        # Only the static-pivot guard needs per-group diag arrays; gating on
        # it keeps the plain path's fusion key to the level's padded shapes,
        # so enabling the guard is the only thing that can change grouping.
        robust = static_pivot is not None
        # Bucketed ragged fusion: quantize each axis's pow2 pad up to a
        # geometric ladder picked from the plan's level-shape histogram, so
        # levels only a factor <= bucket_waste apart share one scan shape.
        # Off the Pallas path all modes execute the same flat XLA step, so
        # bucketed runs also fuse ACROSS modes (group mode becomes "mixed").
        fuse_buckets = fuse_buckets and fuse_levels
        self.fuse_buckets = fuse_buckets
        buckets = plan.level_shape_buckets(bucket_waste) if fuse_buckets else None

        def _bucket(p: int, axis: str) -> int:
            return bucketize(p, buckets[axis]) if buckets is not None else p

        groups: list[_Group] = []
        run: list[tuple] = []
        run_diag: list[np.ndarray] = []
        run_modes: list[str] = []
        run_shape = None

        def _seg_diag(seg, pc: int) -> np.ndarray:
            return _pad_to(plan.diag_idx[seg.cols], pc, pad_key)

        def flush():
            nonlocal run, run_diag, run_modes, run_shape
            if not run:
                return
            stacked = tuple(
                jnp.asarray(np.stack([r[i] for r in run])) for i in range(5)
            )
            diag = None
            if robust:
                diag = jnp.asarray(np.stack(run_diag))
                if len(run) == 1:
                    diag = diag[0]
            mode = run_modes[0] if len(set(run_modes)) == 1 else "mixed"
            groups.append(
                _Group(kind="scan" if len(run) > 1 else "flat",
                       arrays=stacked, mode=mode, diag=diag,
                       n_levels=len(run))
            )
            run, run_diag, run_modes, run_shape = [], [], [], None

        for seg in plan.segments:
            if seg.level >= level_cut:
                break  # replaced by the dense trailing block
            mode = mode_override or seg.mode
            if mode in disable_modes:
                mode = MODE_FLAT if mode != MODE_FLAT else MODE_SEGMENTED
            if use_pallas and mode in (MODE_SEGMENTED, MODE_PANEL) and seg.n_upd:
                flush()
                groups.append(
                    _Group(kind="pallas",
                           arrays=_build_pallas_layout(plan, seg, pad_key),
                           mode=mode,
                           diag=(jnp.asarray(_seg_diag(seg, _pow2(len(seg.cols))))
                                 if robust else None))
                )
                continue
            ns, us = seg.norm_slice, seg.upd_slice
            pn = _bucket(_pow2(seg.n_norm), "norm")
            pu = _bucket(_pow2(seg.n_upd), "upd")
            pc = _bucket(_pow2(len(seg.cols)), "cols")
            arrs = (
                _pad_to(plan.norm_idx[ns], pn, pad_key),
                _pad_to(plan.norm_diag[ns], pn, pad_key),
                _pad_to(plan.lidx[us], pu, pad_key),
                _pad_to(plan.uidx[us], pu, pad_key),
                _pad_to(plan.didx[us], pu, pad_key),
            )
            if fuse_buckets:
                # execution is mode-agnostic here, so the key is shape-only
                shape = (pn, pu, pc) if robust else (pn, pu)
            else:
                shape = (pn, pu, pc, mode) if robust else (pn, pu, mode)
            if fuse_levels and shape == run_shape:
                run.append(arrs)
                if robust:
                    run_diag.append(_seg_diag(seg, pc))
                run_modes.append(mode)
            else:
                flush()
                run = [arrs]
                run_diag = [_seg_diag(seg, pc)] if robust else []
                run_modes = [mode]
                run_shape = shape
            if not fuse_levels:
                flush()
        flush()
        if self.dense_tail_info is not None:
            c_star = self.dense_tail_info["c_star"]
            tail_diag = None
            if robust:
                tail_diag = jnp.asarray(_pad_to(
                    plan.diag_idx[c_star:], _pow2(plan.n - c_star), pad_key))
            groups.append(_Group(kind="dense", arrays=self._dense_tail,
                                 mode="dense", diag=tail_diag))
        self._groups = groups
        # what one factorize walks before the dense block, from the built
        # groups: sparse levels and their padded scatter-add entries
        sparse = [g for g in groups if g.kind != "dense"]
        self.sparse_levels = sum(g.n_levels for g in sparse)
        self.update_entries = sum(int(np.prod(g.arrays[2].shape))
                                  for g in sparse)

        # Static schedule signature + pytree views for the fused runner.
        self.jit_schedule = jit_schedule
        self._exec_cache = resolve_executable_cache(executable_cache)
        self._kinds = tuple(g.kind for g in groups)
        self._group_arrays = tuple(g.arrays for g in groups)
        self._group_diags = tuple(g.diag for g in groups)
        if self.shard is not None:
            # plan metadata gets an explicitly replicated NamedSharding so
            # the sharded runner never re-lays it out per call
            self._a_scatter = self.shard.replicate(self._a_scatter)
            self._group_arrays = self.shard.replicate(self._group_arrays)
            self._group_diags = self.shard.replicate(self._group_diags)
        self.n_groups = len(groups)
        # dispatch count of the most recent factorize* call (1 on the fused
        # path; one per jitted group call — plus entry scatter — otherwise)
        self.last_n_dispatches = 0

    # -- whole-schedule fused path -----------------------------------------

    def _shard_for_batch(self, batched: bool, batch: Optional[int]):
        """The ScenarioSharding to run under, or None: sharding applies only
        to batched entries whose batch divides the shard count (the facade
        pads; direct callers silently fall back, mirroring the
        silent-replicate rule in distributed/sharding.py)."""
        if self.shard is None or not batched:
            return None
        if batch is not None and batch % self.shard.n_shards != 0:
            return None
        return self.shard

    def _runner_key(self, entry: str, batched: bool, shard=None):
        robust = self.static_pivot is not None
        return ("factorize", self.plan.digest, entry, batched, self._kinds,
                np.dtype(self.dtype).str, robust, self.use_pallas,
                self.interpret, self.nnz,
                None if shard is None else shard.descriptor,
                self.layout.name)

    def _runner_for(self, entry: str, batched: bool, shard=None):
        robust = self.static_pivot is not None
        return self._exec_cache.get_or_build(
            self._runner_key(entry, batched, shard),
            lambda: _build_factorize_runner(
                self._kinds, entry=entry, batched=batched, robust=robust,
                interpret=self.interpret, use_pallas=self.use_pallas,
                nnz=self.nnz, dtype=self.storage_dtype,
                planar=self.layout.planar, shard=shard))

    def _factorize_fused(self, a, *, entry: str, batched: bool) -> jnp.ndarray:
        robust = self.static_pivot is not None
        shard = self._shard_for_batch(batched, a.shape[0] if batched else None)
        runner = self._runner_for(entry, batched, shard)
        eps = (jnp.asarray(self.static_pivot, dtype=self.storage_dtype)
               if robust else None)
        out = runner(a, self._a_scatter, self._group_arrays,
                     self._group_diags, eps)
        self.last_n_dispatches = 1
        self.last_n_perturbed_global = None
        if robust:
            if shard is not None:
                (vals, self.last_a_max, self.last_n_perturbed,
                 self.last_n_perturbed_global) = out
            else:
                vals, self.last_a_max, self.last_n_perturbed = out
        else:
            vals = out
            self.last_a_max = None
            self.last_n_perturbed = None
        return vals

    def _jitted_steps(self, batched: bool) -> dict:
        """Jitted per-group step functions for this layout (non-fused path)."""
        from ..kernels import ops as kops

        if self.layout.planar:
            if batched:
                return dict(
                    scan=_scan_steps_planar_batched,
                    scan_robust=_scan_steps_robust_planar_batched,
                    flat=_level_step_planar_batched,
                    flat_robust=_level_step_robust_planar_batched,
                    pallas=kops.level_update_planar_batched,
                    perturb=kops.perturb_diags_planar_batched,
                    dense=_dense_tail_step_planar_batched,
                )
            return dict(
                scan=_scan_steps_planar,
                scan_robust=_scan_steps_robust_planar,
                flat=_level_step_planar,
                flat_robust=_level_step_robust_planar,
                pallas=kops.level_update_planar,
                perturb=kops.perturb_diags_planar,
                dense=_dense_tail_step_planar,
            )
        if batched:
            return dict(
                scan=_scan_steps_batched, scan_robust=_scan_steps_robust_batched,
                flat=_level_step_batched, flat_robust=_level_step_robust_batched,
                pallas=kops.level_update_batched,
                perturb=kops.perturb_diags_batched,
                dense=_dense_tail_step_batched,
            )
        return dict(
            scan=_scan_steps, scan_robust=_scan_steps_robust,
            flat=_level_step, flat_robust=_level_step_robust,
            pallas=kops.level_update, perturb=kops.perturb_diags,
            dense=_dense_tail_step,
        )

    def factorize(self, a_vals) -> jnp.ndarray:
        """Scatter A values into the filled pattern and factorize in place."""
        a = jnp.asarray(a_vals, dtype=self.dtype)
        if self.jit_schedule:
            # scatter folded into the fused program: no separate un-donated
            # nnz-sized zeros+set dispatch per refactorization (planar
            # layouts also pack re/im planes inside the program)
            return self._factorize_fused(a, entry="scatter", batched=False)
        if self.layout.planar:
            a = pack_planes(a, self.storage_dtype)
        vals = jnp.zeros(self.layout.storage_shape(self.nnz),
                         dtype=self.storage_dtype)
        vals = vals.at[self._a_scatter].set(a)
        out = self.factorize_filled(vals)
        self.last_n_dispatches += 1     # the entry scatter
        return out

    def factorize_filled(self, vals: jnp.ndarray) -> jnp.ndarray:
        if self.jit_schedule:
            return self._factorize_fused(
                jnp.asarray(vals, dtype=self.storage_dtype), entry="filled",
                batched=False)
        step = self._jitted_steps(batched=False)
        robust = self.static_pivot is not None
        self.last_n_perturbed_global = None
        n_dispatch = 0
        if robust:
            mag = pabs(vals) if self.layout.planar else jnp.abs(vals)
            self.last_a_max = a_max = jnp.max(mag)
            tau = jnp.asarray(self.static_pivot,
                              dtype=self.storage_dtype) * a_max
            counts = []
            n_dispatch += 1
        else:
            # no extra dispatch on the plain hot path; diagnostics that
            # need max|A| recompute it lazily from the caller's retained
            # A values (GLU.solve_info does)
            self.last_a_max = None
            self.last_n_perturbed = None
        for g in self._groups:
            if g.kind == "scan":
                if robust:
                    vals, c = step["scan_robust"](vals, g.diag, tau, *g.arrays)
                    counts.append(c)
                else:
                    vals = step["scan"](vals, *g.arrays)
                n_dispatch += 1
            elif g.kind == "pallas":
                if robust:
                    vals, c = step["perturb"](vals, g.diag, tau)
                    counts.append(c)
                    n_dispatch += 1
                vals = step["pallas"](vals, *g.arrays, interpret=self.interpret)
                n_dispatch += 1
            elif g.kind == "dense":
                if robust:
                    vals, c = step["perturb"](vals, g.diag, tau)
                    counts.append(c)
                    n_dispatch += 1
                vals = step["dense"](vals, *g.arrays, interpret=self.interpret,
                                     use_pallas=self.use_pallas)
                n_dispatch += 1
            else:
                if robust:
                    vals, c = step["flat_robust"](vals, g.diag, tau,
                                                  *(a[0] for a in g.arrays))
                    counts.append(c)
                else:
                    vals = step["flat"](vals, *(a[0] for a in g.arrays))
                n_dispatch += 1
        if robust:
            self.last_n_perturbed = sum(counts) if counts \
                else jnp.asarray(0, dtype=jnp.int32)
        self.last_n_dispatches = n_dispatch
        return vals

    # -- batched refactorization (one plan, many matrices) -------------------
    def factorize_batched(self, a_vals_batch) -> jnp.ndarray:
        """Factorize B matrices sharing this plan's pattern in lockstep.

        ``a_vals_batch``: (B, nnz_A) values, one row per matrix, in A's
        entry order.  Returns (B, nnz_filled) factored values — row ``i``
        equals ``factorize(a_vals_batch[i])``.  Every level-group runs as a
        single device dispatch for the whole batch.
        """
        a = jnp.asarray(a_vals_batch, dtype=self.dtype)
        if a.ndim != 2:
            raise ValueError(f"expected (B, nnz_A) values, got shape {a.shape}")
        if self.jit_schedule:
            return self._factorize_fused(a, entry="scatter", batched=True)
        if self.layout.planar:
            a = pack_planes(a, self.storage_dtype)
        vals = jnp.zeros(self.layout.storage_shape(a.shape[0], self.nnz),
                         dtype=self.storage_dtype)
        vals = vals.at[:, self._a_scatter].set(a)
        out = self.factorize_filled_batched(vals)
        self.last_n_dispatches += 1     # the entry scatter
        return out

    def factorize_filled_batched(self, vals: jnp.ndarray) -> jnp.ndarray:
        if self.jit_schedule:
            return self._factorize_fused(
                jnp.asarray(vals, dtype=self.storage_dtype), entry="filled",
                batched=True)
        step = self._jitted_steps(batched=True)
        robust = self.static_pivot is not None
        self.last_n_perturbed_global = None
        n_dispatch = 0
        if robust:
            mag = pabs(vals) if self.layout.planar else jnp.abs(vals)
            self.last_a_max = jnp.max(mag, axis=1)  # (B,)
            tau = jnp.asarray(self.static_pivot,
                              dtype=self.storage_dtype) * self.last_a_max
            counts = []
            n_dispatch += 1
        else:
            self.last_a_max = None
            self.last_n_perturbed = None
        for g in self._groups:
            if g.kind == "scan":
                if robust:
                    vals, c = step["scan_robust"](vals, g.diag, tau, *g.arrays)
                    counts.append(c)
                else:
                    vals = step["scan"](vals, *g.arrays)
                n_dispatch += 1
            elif g.kind == "pallas":
                if robust:
                    vals, c = step["perturb"](vals, g.diag, tau)
                    counts.append(c)
                    n_dispatch += 1
                vals = step["pallas"](vals, *g.arrays, interpret=self.interpret)
                n_dispatch += 1
            elif g.kind == "dense":
                if robust:
                    vals, c = step["perturb"](vals, g.diag, tau)
                    counts.append(c)
                    n_dispatch += 1
                vals = step["dense"](vals, *g.arrays)
                n_dispatch += 1
            else:
                if robust:
                    vals, c = step["flat_robust"](vals, g.diag, tau,
                                                  *(a[0] for a in g.arrays))
                    counts.append(c)
                else:
                    vals = step["flat"](vals, *(a[0] for a in g.arrays))
                n_dispatch += 1
        if robust:
            self.last_n_perturbed = sum(counts) if counts \
                else jnp.zeros(vals.shape[0], dtype=jnp.int32)
        self.last_n_dispatches = n_dispatch
        return vals

    __call__ = factorize
