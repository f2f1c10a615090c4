"""Level-scheduled sparse triangular solves (Ly = b, Ux = y) in JAX,
plus batched iterative refinement on the device factors.

The forward sweep reuses the factorization levels (its dependency rule —
column j must wait for all c < j with L(j,c) != 0 — is exactly the paper's
"look left" relaxed rule, so the same levelization is valid).  The backward
sweep uses U-row levels computed at plan time.

Sparse right-hand sides: circuit RHS vectors are mostly zeros (an AC
excitation is often 1-2 entries), and the solution of ``L y = b`` is
supported exactly on the reach of ``nonzeros(b)`` in L's DAG (Gilbert-
Peierls; cf. Ruipeng Li, arXiv 1710.04985).  ``solve(..., rhs_pattern=...)``
prunes the level-group schedule to that reach — entries whose source column
is outside the closure contribute exact zeros and are dropped wholesale, so
the pruned solve is bit-identical to the full one on the reach.  Pruned
schedules are cached per rhs pattern (the contract is many solves per
pattern: a fixed excitation across a sweep).

Dense trailing block: when the factorizer finishes the columns [c*, n) as
one dense LU (``JaxFactorizer.dense_tail_info``), the solve runs that block
as dense substitution instead of walking its hundreds of one-column levels
by indexed gather and scatter.  A solve is then: the prefix's forward
levels (their L entries into tail rows included), one gather of the
factored block from ``vals``, unit-lower forward and upper backward
substitution on it, and the prefix's backward levels re-levelled on the
prefix columns, whose first level also subtracts the tail columns' U
entries from the prefix rows.  Planar solves keep the level walk.

Refinement runs on whatever system the factors describe (for the GLU facade
that is the scaled + permuted one): each sweep computes ``r = b - A x`` with
a sparse SpMV of A's values, the componentwise backward error
``max_i |r_i| / (|A||x| + |b|)_i`` as the stopping test, and — while above
tolerance — one more triangular solve on the existing factors.  Sweeps are
issued in chunks of ``sync_every`` with the convergence mask applied on
device, so the common ``refine <= 2`` case costs exactly ONE device->host
sync instead of one per sweep (``host_syncs`` in the returned info counts
them).
"""
from __future__ import annotations

from collections import OrderedDict
from functools import partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..kernels.ops import masked_correction, spmv
from ..sparse.layout import pack_planes, pdiv, pmul, unpack_planes
from ..spans import named, span
from .executor import resolve_executable_cache
from .factorize import dense_tail_positions
from .plan import FactorizePlan, bucketize, choose_buckets, pow2_pad

__all__ = ["JaxTriangularSolver", "trisolve_numpy"]

# unroll factor of the dense-tail substitution loops: 8 takes a 726-step
# forward + backward pair from 6.73 to 6.20 ms on a TPU v5e (f64)
_DENSE_TAIL_UNROLL = 8


def trisolve_numpy(plan: FactorizePlan, vals: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Sequential oracle: unit-lower forward then upper backward solve."""
    n, indptr, indices = plan.n, plan.indptr, plan.indices
    vals = np.asarray(vals)
    dtype = np.result_type(vals.dtype, np.asarray(b).dtype, np.float64)
    vals = vals.astype(dtype, copy=False)
    x = np.array(b, dtype=dtype, copy=True)
    for j in range(n):
        e = int(indptr[j + 1])
        dp = int(plan.diag_idx[j])
        rows = indices[dp + 1 : e]
        x[rows] -= vals[dp + 1 : e] * x[j]
    for j in range(n - 1, -1, -1):
        s = int(indptr[j])
        dp = int(plan.diag_idx[j])
        x[j] /= vals[dp]
        rows = indices[s:dp]
        x[rows] -= vals[s:dp] * x[j]
    return x


def _pad_i32(x: np.ndarray, size: int, fill: int) -> np.ndarray:
    out = np.full(size, fill, dtype=np.int32)
    out[: len(x)] = x
    return out


_pow2 = pow2_pad


def _fwd_group_body(vals, b, rows, cols, vidx):
    def body(bb, xs):
        r, c, v = xs
        lv = vals.at[v].get(mode="fill", fill_value=0.0)
        xc = bb.at[c].get(mode="fill", fill_value=0.0)
        return bb.at[r].add(-lv * xc, mode="drop"), None

    b, _ = jax.lax.scan(body, b, (rows, cols, vidx))
    return b


def _bwd_group_body(vals, b, lcols, ldiag, rows, cols, vidx):
    def body(bb, xs):
        lc, ld, r, c, v = xs
        dv = vals.at[ld].get(mode="fill", fill_value=1.0)
        xj = bb.at[lc].get(mode="fill", fill_value=0.0) / dv
        bb = bb.at[lc].set(xj, mode="drop")
        uv = vals.at[v].get(mode="fill", fill_value=0.0)
        xc = bb.at[c].get(mode="fill", fill_value=0.0)
        return bb.at[r].add(-uv * xc, mode="drop"), None

    b, _ = jax.lax.scan(body, b, (lcols, ldiag, rows, cols, vidx))
    return b


def _residual_berr_body(rows, cols, a_vals, a_abs, x, b, n):
    """r = b - A x and the componentwise backward error in one dispatch.
    Zero denominators (a row with |A||x| + |b| == 0) count as converged
    when the residual there is zero and as inf otherwise."""
    r = b - spmv(rows, cols, a_vals, x, n_rows=n)
    denom = spmv(rows, cols, a_abs, jnp.abs(x), n_rows=n) + jnp.abs(b)
    berr = jnp.max(jnp.where(denom > 0, jnp.abs(r) / denom,
                             jnp.where(jnp.abs(r) > 0, jnp.inf, 0.0)))
    return r, berr


# every variant runs as the program ``glu_residual``
@partial(jax.jit, static_argnames=("n",))
@partial(named, "glu_residual")
def _residual_berr(rows, cols, a_vals, a_abs, x, b, *, n):
    return _residual_berr_body(rows, cols, a_vals, a_abs, x, b, n)


@partial(jax.jit, static_argnames=("n",))
@partial(named, "glu_residual")
def _residual_berr_batched(rows, cols, a_vals, a_abs, x, b, *, n):
    return jax.vmap(
        lambda av, aa, xx, bb: _residual_berr_body(rows, cols, av, aa, xx, bb, n)
    )(a_vals, a_abs, x, b)


# Many-RHS twin: one value vector, (K, n) right-hand sides.
@partial(jax.jit, static_argnames=("n",))
@partial(named, "glu_residual")
def _residual_berr_multi(rows, cols, a_vals, a_abs, x, b, *, n):
    return jax.vmap(
        lambda xx, bb: _residual_berr_body(rows, cols, a_vals, a_abs, xx, bb, n)
    )(x, b)


# Planar twins: ``vals`` is (nnz, 2) split re/im planes and the running
# solution carries (n, 2) planes — the complex MAC / divide run on real
# operands (pmul/pdiv).  Index gathers are layout-agnostic (they gather
# plane ROWS), so the level-group schedule is shared with the native path.
def _fwd_group_planar_body(vals, b, rows, cols, vidx):
    def body(bb, xs):
        r, c, v = xs
        lv = vals.at[v].get(mode="fill", fill_value=0.0)     # (P, 2)
        xc = bb.at[c].get(mode="fill", fill_value=0.0)
        return bb.at[r].add(-pmul(lv, xc), mode="drop"), None

    b, _ = jax.lax.scan(body, b, (rows, cols, vidx))
    return b


def _bwd_group_planar_body(vals, b, lcols, ldiag, rows, cols, vidx):
    def body(bb, xs):
        lc, ld, r, c, v = xs
        # padded ldiag slots read (1, 1) planes; the pdiv result there is
        # discarded by the dropped set, same as the native fill_value=1.0
        dv = vals.at[ld].get(mode="fill", fill_value=1.0)
        xj = pdiv(bb.at[lc].get(mode="fill", fill_value=0.0), dv)
        bb = bb.at[lc].set(xj, mode="drop")
        uv = vals.at[v].get(mode="fill", fill_value=0.0)
        xc = bb.at[c].get(mode="fill", fill_value=0.0)
        return bb.at[r].add(-pmul(uv, xc), mode="drop"), None

    b, _ = jax.lax.scan(body, b, (lcols, ldiag, rows, cols, vidx))
    return b


_fwd_group = partial(jax.jit, donate_argnums=(1,))(_fwd_group_body)
_bwd_group = partial(jax.jit, donate_argnums=(1,))(_bwd_group_body)
_fwd_group_planar = partial(jax.jit, donate_argnums=(1,))(_fwd_group_planar_body)
_bwd_group_planar = partial(jax.jit, donate_argnums=(1,))(_bwd_group_planar_body)

# Batched twins: vals (B, nnz) and b (B, n) share the level-group index
# arrays, so each group stays ONE dispatch for the whole batch.
_fwd_group_batched = partial(jax.jit, donate_argnums=(1,))(
    jax.vmap(_fwd_group_body, in_axes=(0, 0, None, None, None)))
_bwd_group_batched = partial(jax.jit, donate_argnums=(1,))(
    jax.vmap(_bwd_group_body, in_axes=(0, 0, None, None, None, None, None)))

# Many-RHS twins: ONE factor value vector shared by every rhs row — the
# adjoint/sensitivity workload (K seeds against one factorization).
_fwd_group_multi = partial(jax.jit, donate_argnums=(1,))(
    jax.vmap(_fwd_group_body, in_axes=(None, 0, None, None, None)))
_bwd_group_multi = partial(jax.jit, donate_argnums=(1,))(
    jax.vmap(_bwd_group_body, in_axes=(None, 0, None, None, None, None, None)))

_fwd_group_planar_batched = partial(jax.jit, donate_argnums=(1,))(
    jax.vmap(_fwd_group_planar_body, in_axes=(0, 0, None, None, None)))
_bwd_group_planar_batched = partial(jax.jit, donate_argnums=(1,))(
    jax.vmap(_bwd_group_planar_body,
             in_axes=(0, 0, None, None, None, None, None)))
_fwd_group_planar_multi = partial(jax.jit, donate_argnums=(1,))(
    jax.vmap(_fwd_group_planar_body, in_axes=(None, 0, None, None, None)))
_bwd_group_planar_multi = partial(jax.jit, donate_argnums=(1,))(
    jax.vmap(_bwd_group_planar_body,
             in_axes=(None, 0, None, None, None, None, None)))


# -- dense trailing block ----------------------------------------------------

def _prefix_bwd_levels(plan: FactorizePlan, c_star: int):
    """Backward levels of the prefix columns [0, c*) once the tail is
    solved.  Level 0 applies the tail columns' U entries in prefix rows
    (their x is final) beside the prefix columns that wait on nothing, so
    a prefix column waits on the tail at most one level.  Returns the
    plan's ``bwd_*`` arrays (ptr, rows, cols, vidx, level_cols, col_ptr)
    restricted to prefix rows and re-levelled."""
    rows, cols, vidx = plan.bwd_rows, plan.bwd_cols, plan.bwd_vidx
    lev = np.zeros(plan.n, dtype=np.int64)
    # the plan's levels are a topological order, so a column's new level
    # is final before its own entries are visited; tail columns stay at 0
    for l in range(len(plan.bwd_ptr) - 1):
        s, e = int(plan.bwd_ptr[l]), int(plan.bwd_ptr[l + 1])
        rr = rows[s:e]
        m = rr < c_star
        if m.any():
            np.maximum.at(lev, rr[m], lev[cols[s:e][m]] + 1)
    keep = rows < c_star
    rows, cols, vidx = rows[keep], cols[keep], vidx[keep]
    srt = np.argsort(lev[cols], kind="stable")
    rows, cols, vidx = rows[srt], cols[srt], vidx[srt]
    nlev = int(lev[:c_star].max(initial=0)) + 1
    ptr = np.searchsorted(lev[cols], np.arange(nlev + 1))
    level_cols = np.argsort(lev[:c_star], kind="stable").astype(np.int64)
    col_ptr = np.searchsorted(lev[level_cols], np.arange(nlev + 1))
    return ptr, rows, cols, vidx, level_cols, col_ptr


def _tail_step_body(vals, x, tail_cols):
    """Dense unit-lower forward then upper backward substitution of
    x[c*:] on the factored trailing block, gathered from ``vals`` column
    by column: row j of ``tail_cols`` holds the value indices of the
    block's column j (``nnz``, read as 0, where the pattern has none).
    Each step takes one column of L or U, elementwise with no reduction,
    so its rounding is the same batched, sharded or not."""
    size = tail_cols.shape[0]
    c = x.shape[0] - size
    cols = vals.at[tail_cols].get(mode="fill", fill_value=0.0)
    i = jnp.arange(size)

    def fwd(j, y):
        col = jax.lax.dynamic_index_in_dim(cols, j, keepdims=False)
        yj = jax.lax.dynamic_index_in_dim(y, j, keepdims=False)
        return jnp.where(i > j, y - col * yj, y)

    def bwd(k, y):
        j = size - 1 - k
        col = jax.lax.dynamic_index_in_dim(cols, j, keepdims=False)
        yj = (jax.lax.dynamic_index_in_dim(y, j, keepdims=False)
              / jax.lax.dynamic_index_in_dim(col, j, keepdims=False))
        return jnp.where(i < j, y - col * yj, jnp.where(i == j, yj, y))

    y = jax.lax.fori_loop(0, size, fwd, x[c:], unroll=_DENSE_TAIL_UNROLL)
    y = jax.lax.fori_loop(0, size, bwd, y, unroll=_DENSE_TAIL_UNROLL)
    return jnp.concatenate([x[:c], y])


# Per-kind twins of the dense-tail step for the ``jit_schedule=False``
# path: one dispatch.
_TAIL_STEP = {
    "single": jax.jit(_tail_step_body),
    "batched": jax.jit(jax.vmap(_tail_step_body, in_axes=(0, 0, None))),
    "multi": jax.jit(jax.vmap(_tail_step_body, in_axes=(None, 0, None))),
}


# -- whole-schedule fused trisolve -----------------------------------------
#
# One jitted program runs the forward sweep, the backward sweep, and the
# dtype cast of the rhs — a triangular solve is a single device dispatch
# instead of one per level group.  Neither ``vals`` (caller retains the
# factors) nor ``b`` (caller's rhs) is donated, which also removes the
# defensive rhs copy the per-group path needs.

def _solve_schedule_body(vals, b, fwd, bwd, tail=None):
    """``tail`` (the block's column position map, or None) runs the
    dense-tail step between the prefix walks."""
    x = jnp.asarray(b, dtype=vals.dtype)
    for g in fwd:
        x = _fwd_group_body(vals, x, *g)
    if tail is not None:
        x = _tail_step_body(vals, x, tail)
    for g in bwd:
        x = _bwd_group_body(vals, x, *g)
    return x


def _solve_schedule_planar_body(vals, b, fwd, bwd, tail=None):
    # planes in, native complex out: the rhs is packed INSIDE the fused
    # program and the solution unpacked at the end, so a planar triangular
    # solve still presents the complex interface in ONE dispatch.  Planar
    # solves walk the whole schedule: no dense-tail step
    assert tail is None
    x = pack_planes(b, vals.dtype)
    for g in fwd:
        x = _fwd_group_planar_body(vals, x, *g)
    for g in bwd:
        x = _bwd_group_planar_body(vals, x, *g)
    return unpack_planes(x)


def _build_trisolve_runner(kind: str, planar: bool = False, shard=None):
    body = _solve_schedule_planar_body if planar else _solve_schedule_body
    # arguments: (vals, b, fwd, bwd, tail); tail is None without a dense
    # tail
    if kind == "single":
        fn = body
    elif kind == "batched":
        fn = jax.vmap(body, in_axes=(0, 0, None, None, None))
    else:  # "multi"
        fn = jax.vmap(body, in_axes=(None, 0, None, None, None))
    if shard is not None:
        if kind != "batched":
            raise ValueError("scenario sharding requires the batched kind")
        # value and rhs batches split along the scenario axes, the
        # schedule is replicated; each shard's trisolve stays one dispatch.
        # Rows never interact, so the result is bit-identical to unsharded.
        bspec = shard.spec
        fn = jax.shard_map(fn, mesh=shard.mesh,
                           in_specs=(bspec, bspec, P(), P(), P()),
                           out_specs=bspec, check_vma=False)
    return jax.jit(named("glu_trisolve", fn))


class JaxTriangularSolver:
    """solve(vals, b): forward+backward substitution on the factored values."""

    # pruned schedules kept per rhs pattern; enough for a handful of distinct
    # excitation/seed patterns without growing unboundedly under adversarial use
    SPARSE_SCHEDULE_CAP = 32

    def __init__(self, plan: FactorizePlan, fuse: bool = True,
                 fuse_buckets: bool = True, bucket_waste: float = 4.0,
                 jit_schedule: bool = True, executable_cache="default",
                 layout: str = "native", shard=None,
                 dense_tail: Optional[dict] = None):
        """``dense_tail``: the factorizer's ``dense_tail_info`` (or None).
        When given, on the native layout, the trailing block is solved
        densely, on the block each solve gathers from ``vals``."""
        if layout not in ("native", "planar"):
            raise ValueError(
                f"layout must be 'native' or 'planar', got {layout!r} "
                "(the solver has no dtype to resolve 'auto' against)")
        self.plan = plan
        # scenario sharding for batched solves (see JaxFactorizer): single
        # and multi-RHS kinds, and batches not divisible by the shard
        # count, fall back to the unsharded runner
        self.shard = shard if (shard is not None and shard.n_shards > 1) \
            else None
        # planar: factor values arrive as (nnz, 2) / (B, nnz, 2) split re/im
        # planes; rhs and solution stay native complex at the interface
        self.layout = layout
        self._planar = layout == "planar"
        self._fuse = fuse
        self._fuse_buckets = fuse_buckets and fuse
        self._bucket_waste = bucket_waste
        self.jit_schedule = jit_schedule
        self._exec_cache = resolve_executable_cache(executable_cache)
        # dispatch count of the most recent solve* call (1 on the fused
        # path; one per level group plus the rhs copy otherwise)
        self.last_n_dispatches = 0
        # the most recent trisolve's dense-tail size (0: not engaged) and
        # its padded gather/scatter entries, forward and backward
        self.last_dense_tail = 0
        self.last_indexed_entries = 0
        self.dense_tail_info = None if self._planar else dense_tail
        self._tail = None
        self._n_fwd_levels = len(plan.fwd_ptr) - 1
        self._bwd_levels = (plan.bwd_ptr, plan.bwd_rows, plan.bwd_cols,
                            plan.bwd_vidx, plan.bwd_level_cols,
                            plan.bwd_col_ptr)
        if self.dense_tail_info is not None:
            # the sparse walks cover the prefix columns [0, c*) alone: the
            # forward levels before the cut, the backward levels re-levelled
            c_star = int(self.dense_tail_info["c_star"])
            self._n_fwd_levels = int(self.dense_tail_info["level_cut"])
            self._bwd_levels = _prefix_bwd_levels(plan, c_star)
            size = plan.n - c_star
            self._tail = jnp.asarray(
                dense_tail_positions(plan, c_star, size).T)
        self._full_schedule = self._build_schedule(None, None)
        if self.shard is not None:
            # schedule index arrays are replicated once so the sharded
            # runner never re-lays them out per call
            self._full_schedule = self.shard.replicate(self._full_schedule)
            if self._tail is not None:
                self._tail = self.shard.replicate(self._tail)
        self._sparse_schedules: OrderedDict = OrderedDict()

    def _build_schedule(self, fwd_mask, bwd_mask):
        """Level-group schedule as (fwd_groups, bwd_groups).  ``fwd_mask`` /
        ``bwd_mask`` (boolean (n,) column masks) restrict the schedule to
        the masked columns; levels left empty are dropped entirely (fewer
        scheduled steps is where the sparse-RHS win comes from).  With a
        dense tail the groups cover the prefix columns only.

        With ``fuse_buckets`` the per-level pow2 pads are quantized up to a
        geometric ladder built from THIS schedule's level-size histogram, so
        runs of near-equal levels share one scan shape (the pad indices are
        inert, making over-padding bit-safe)."""
        plan, fuse = self.plan, self._fuse
        n = plan.n
        pad_row = n  # out-of-range -> drop
        pad_v = plan.nnz

        def make_pad(sizes_list):
            """size -> padded size, via the bucket ladder of this schedule."""
            if not self._fuse_buckets:
                return _pow2
            ladder = choose_buckets(np.asarray(sizes_list, dtype=np.int64),
                                    max_waste=self._bucket_waste)
            return lambda x: bucketize(_pow2(x), ladder)

        def build_groups(items):
            groups, run, run_shape = [], [], None

            def flush():
                nonlocal run, run_shape
                if run:
                    groups.append(
                        tuple(jnp.asarray(np.stack([r[i] for r in run]))
                              for i in range(len(run[0])))
                    )
                run, run_shape = [], None

            for arrs, shape in items:
                if fuse and shape == run_shape:
                    run.append(arrs)
                else:
                    flush()
                    run, run_shape = [arrs], shape
            flush()
            return groups

        fwd_raw = []
        for l in range(self._n_fwd_levels):
            s, e = int(plan.fwd_ptr[l]), int(plan.fwd_ptr[l + 1])
            rows = plan.fwd_rows[s:e]
            cols = plan.fwd_cols[s:e]
            vidx = plan.fwd_vidx[s:e]
            if fwd_mask is not None:
                keep = fwd_mask[cols]
                if not keep.any():
                    continue
                rows, cols, vidx = rows[keep], cols[keep], vidx[keep]
            fwd_raw.append((rows, cols, vidx))
        fpad = make_pad([len(r[0]) for r in fwd_raw] or [1])
        fwd_items = []
        for rows, cols, vidx in fwd_raw:
            p = fpad(len(rows))
            fwd_items.append((
                (
                    _pad_i32(rows, p, pad_row),
                    _pad_i32(cols, p, pad_row),
                    _pad_i32(vidx, p, pad_v),
                ),
                p,
            ))
        fwd_groups = build_groups(fwd_items)

        bwd_raw = []
        bptr, brows, bcols, bvidx, blcols, bcol_ptr = self._bwd_levels
        diag = plan.diag_idx
        for l in range(len(bptr) - 1):
            s, e = int(bptr[l]), int(bptr[l + 1])
            cs, ce = int(bcol_ptr[l]), int(bcol_ptr[l + 1])
            lcols = blcols[cs:ce]
            rows = brows[s:e]
            cols = bcols[s:e]
            vidx = bvidx[s:e]
            if bwd_mask is not None:
                keepc = bwd_mask[lcols]
                keepu = bwd_mask[cols]
                if not keepc.any() and not keepu.any():
                    continue
                lcols = lcols[keepc]
                rows, cols, vidx = rows[keepu], cols[keepu], vidx[keepu]
            bwd_raw.append((lcols, rows, cols, vidx))
        cpad = make_pad([len(r[0]) for r in bwd_raw] or [1])
        upad = make_pad([len(r[1]) for r in bwd_raw] or [1])
        bwd_items = []
        for lcols, rows, cols, vidx in bwd_raw:
            pc = cpad(len(lcols))
            pu = upad(len(rows))
            bwd_items.append((
                (
                    _pad_i32(lcols, pc, pad_row),
                    _pad_i32(diag[lcols], pc, pad_v),
                    _pad_i32(rows, pu, pad_row),
                    _pad_i32(cols, pu, pad_row),
                    _pad_i32(vidx, pu, pad_v),
                ),
                (pc, pu),
            ))
        bwd_groups = build_groups(bwd_items)
        return fwd_groups, bwd_groups

    # -- sparse-RHS schedule cache -------------------------------------------
    @staticmethod
    def _normalize_pattern(rhs_pattern) -> np.ndarray:
        pat = np.unique(np.asarray(rhs_pattern, dtype=np.int64).ravel())
        return pat

    def schedule_for_pattern(self, rhs_pattern):
        """The pruned (fwd_groups, bwd_groups, fwd_reach, bwd_reach) for a
        rhs supported on ``rhs_pattern``; memoized per pattern (LRU)."""
        pat = self._normalize_pattern(rhs_pattern)
        key = pat.tobytes()
        hit = self._sparse_schedules.get(key)
        if hit is not None:
            self._sparse_schedules.move_to_end(key)
            return hit
        n = self.plan.n
        freach = self.plan.fwd_reach(pat)
        breach = self.plan.bwd_reach(freach)
        if len(freach) == n and len(breach) == n:
            # the reach closure is every column: a "pruned" schedule would be
            # a redundant twin of the full one (same work, its own compiled
            # executables).  Reuse the full schedule OBJECT so the jit /
            # executable caches hit instead of recompiling.
            entry = (self._full_schedule[0], self._full_schedule[1],
                     freach, breach)
        else:
            fmask = np.zeros(n, dtype=bool)
            fmask[freach] = True
            bmask = np.zeros(n, dtype=bool)
            bmask[breach] = True
            fwd_groups, bwd_groups = self._build_schedule(fmask, bmask)
            if self.shard is not None:
                fwd_groups, bwd_groups = self.shard.replicate(
                    (fwd_groups, bwd_groups))
            entry = (fwd_groups, bwd_groups, freach, breach)
        self._sparse_schedules[key] = entry
        while len(self._sparse_schedules) > self.SPARSE_SCHEDULE_CAP:
            self._sparse_schedules.popitem(last=False)
        return entry

    def _groups_for(self, rhs_pattern):
        """(fwd_groups, bwd_groups, schedule_id) for the rhs support; the
        id distinguishes pruned schedules in the executable-cache key."""
        if rhs_pattern is None:
            fwd, bwd = self._full_schedule
            return fwd, bwd, "full"
        fwd, bwd, _, _ = self.schedule_for_pattern(rhs_pattern)
        if fwd is self._full_schedule[0]:       # full-reach shortcut hit
            return fwd, bwd, "full"
        key = self._normalize_pattern(rhs_pattern).tobytes()
        return fwd, bwd, key.hex()

    def _schedule(self, rhs_pattern):
        """(fwd, bwd, tail, schedule_id) of one trisolve; records its
        dense-tail size and padded gather/scatter entries.  ``tail`` is
        None when the plan has none or the rhs's forward reach never enters
        it; the tail is dense, so a reach that enters it takes the whole
        step."""
        fwd, bwd, sid = self._groups_for(rhs_pattern)
        tail = self._tail
        if tail is not None and rhs_pattern is not None:
            freach = self.schedule_for_pattern(rhs_pattern)[2]
            if not (len(freach)
                    and freach[-1] >= self.dense_tail_info["c_star"]):
                tail = None
        self.last_indexed_entries = (
            sum(int(np.prod(g[0].shape)) for g in fwd)
            + sum(int(np.prod(g[2].shape)) for g in bwd))
        self.last_dense_tail = (0 if tail is None
                                else int(self.dense_tail_info["size"]))
        return fwd, bwd, tail, sid

    def _runner(self, kind: str, sid: str, shard=None):
        return self._exec_cache.get_or_build(
            ("trisolve", self.plan.digest, sid, kind,
             None if shard is None else shard.descriptor, self.layout),
            lambda: _build_trisolve_runner(kind, planar=self._planar,
                                           shard=shard))

    def _run_fused(self, kind: str, vals, x, fwd, bwd, tail, sid: str):
        shard = self.shard
        if shard is not None and (kind != "batched"
                                  or vals.shape[0] % shard.n_shards != 0):
            shard = None
        out = self._runner(kind, sid, shard)(vals, x, tuple(fwd), tuple(bwd),
                                             tail)
        self.last_n_dispatches = 1
        return out

    def _iface_dtype(self, vals):
        """The dtype of rhs/solution at the caller interface: the value
        dtype natively, the matching complex dtype for planar planes."""
        if self._planar:
            return np.dtype(np.complex64 if vals.dtype == np.float32
                            else np.complex128)
        return vals.dtype

    # -- solves ---------------------------------------------------------------
    def solve(self, vals: jnp.ndarray, b, rhs_pattern=None) -> jnp.ndarray:
        """With ``rhs_pattern`` (indices of b's nonzero support) the level
        schedule is pruned to the reach closure of the pattern; ``b`` MUST
        be zero outside it (the facade validates this)."""
        fwd, bwd, tail, sid = self._schedule(rhs_pattern)
        if self.jit_schedule:
            return self._run_fused("single", jnp.asarray(vals),
                                   jnp.asarray(b), fwd, bwd, tail, sid)
        if self._planar:
            # pack_planes always allocates, so the donated running buffer
            # never aliases the caller's rhs
            vals = jnp.asarray(vals)
            x = pack_planes(b, vals.dtype)
            for g in fwd:
                x = _fwd_group_planar(vals, x, *g)
            for g in bwd:
                x = _bwd_group_planar(vals, x, *g)
            self.last_n_dispatches = len(fwd) + len(bwd) + 2
            return unpack_planes(x)
        # defensive copy: the jitted group steps donate the rhs buffer, and
        # ``jnp.asarray`` is a no-op on a JAX array already of vals.dtype —
        # without the copy the *caller's* array would be deleted
        x = jnp.array(b, dtype=vals.dtype, copy=True)
        for g in fwd:
            x = _fwd_group(vals, x, *g)
        if tail is not None:
            x = _TAIL_STEP["single"](vals, x, tail)
        for g in bwd:
            x = _bwd_group(vals, x, *g)
        self.last_n_dispatches = (len(fwd) + len(bwd) + 1
                                  + (tail is not None))
        return x

    def solve_batched(self, vals_batch: jnp.ndarray, b_batch,
                      rhs_pattern=None) -> jnp.ndarray:
        """Row i of the result solves with factor values ``vals_batch[i]``
        and right-hand side ``b_batch[i]`` — B solves in lockstep.  A
        ``rhs_pattern`` is shared by the whole batch (union support)."""
        vals = jnp.asarray(vals_batch)
        fwd, bwd, tail, sid = self._schedule(rhs_pattern)
        b = jnp.asarray(b_batch)
        want = 3 if self._planar else 2
        if vals.ndim != want or b.ndim != 2 or vals.shape[0] != b.shape[0]:
            shape = "(B, nnz, 2)" if self._planar else "(B, nnz)"
            raise ValueError(
                f"expected {shape} values and (B, n) rhs, got "
                f"{vals.shape} and {b.shape}")
        if self.jit_schedule:
            return self._run_fused("batched", vals, b, fwd, bwd, tail, sid)
        if self._planar:
            x = pack_planes(b, vals.dtype)
            for g in fwd:
                x = _fwd_group_planar_batched(vals, x, *g)
            for g in bwd:
                x = _bwd_group_planar_batched(vals, x, *g)
            self.last_n_dispatches = len(fwd) + len(bwd) + 2
            return unpack_planes(x)
        # defensive copy — same donation hazard as :meth:`solve`
        x = jnp.array(b, dtype=vals.dtype, copy=True)
        for g in fwd:
            x = _fwd_group_batched(vals, x, *g)
        if tail is not None:
            x = _TAIL_STEP["batched"](vals, x, tail)
        for g in bwd:
            x = _bwd_group_batched(vals, x, *g)
        self.last_n_dispatches = (len(fwd) + len(bwd) + 1
                                  + (tail is not None))
        return x

    def solve_multi(self, vals: jnp.ndarray, b_multi,
                    rhs_pattern=None) -> jnp.ndarray:
        """Many right-hand sides against ONE set of factor values: ``vals``
        is (nnz,), ``b_multi`` is (K, n), each level group is one dispatch
        for all K rhs (the adjoint/sensitivity workload).  A ``rhs_pattern``
        is the union support of all rows."""
        vals = jnp.asarray(vals)
        fwd, bwd, tail, sid = self._schedule(rhs_pattern)
        b = jnp.asarray(b_multi)
        want = 2 if self._planar else 1
        if vals.ndim != want or b.ndim != 2:
            shape = "(nnz, 2)" if self._planar else "(nnz,)"
            raise ValueError(
                f"expected {shape} values and (K, n) rhs, got "
                f"{vals.shape} and {b.shape}")
        if self.jit_schedule:
            return self._run_fused("multi", vals, b, fwd, bwd, tail, sid)
        if self._planar:
            x = pack_planes(b, vals.dtype)
            for g in fwd:
                x = _fwd_group_planar_multi(vals, x, *g)
            for g in bwd:
                x = _bwd_group_planar_multi(vals, x, *g)
            self.last_n_dispatches = len(fwd) + len(bwd) + 2
            return unpack_planes(x)
        x = jnp.array(b, dtype=vals.dtype, copy=True)
        for g in fwd:
            x = _fwd_group_multi(vals, x, *g)
        if tail is not None:
            x = _TAIL_STEP["multi"](vals, x, tail)
        for g in bwd:
            x = _bwd_group_multi(vals, x, *g)
        self.last_n_dispatches = (len(fwd) + len(bwd) + 1
                                  + (tail is not None))
        return x

    # -- iterative refinement -------------------------------------------------
    def _solve_refined_impl(self, kind, vals, b, a_rows, a_cols, a_vals,
                            a_abs, max_iter, tol, rhs_pattern, sync_every):
        """Shared chunked-refinement driver.  The initial solve may use the
        pruned sparse-RHS schedule; corrections solve against a dense
        residual, so they always run the full schedule.  Convergence is
        masked on DEVICE (``masked_correction``) and the backward error only
        crosses to the host once per ``sync_every`` sweeps — the common
        ``max_iter <= sync_every`` case pays exactly one transfer."""
        with span("glu.refine"):
            n = self.plan.n
            # planar factors still refine against the NATIVE complex system:
            # casting b to vals.dtype would truncate a complex rhs to the real
            # plane dtype, so the cast targets the interface dtype instead
            b = jnp.asarray(b, dtype=self._iface_dtype(vals))
            if kind == "single":
                solve = self.solve
                res_fn = _residual_berr
            elif kind == "batched":
                solve = self.solve_batched
                res_fn = _residual_berr_batched
            else:
                solve = self.solve_multi
                res_fn = _residual_berr_multi
            # ``n_disp`` counts every device program this call launches
            x = solve(vals, b, rhs_pattern=rhs_pattern)
            n_disp = self.last_n_dispatches + 1    # + the residual/berr pass
            r, berr = res_fn(a_rows, a_cols, a_vals, a_abs, x, b, n=n)
            iters = jnp.zeros(berr.shape, dtype=jnp.int32)
            # jnp.zeros launches convert_element_type, plus broadcast_in_dim
            # for a vector
            n_disp += 1 if berr.ndim == 0 else 2
            syncs = 0
            done = 0
            berr_h = iters_h = None
            while done < max_iter:
                chunk = min(max(1, int(sync_every)), max_iter - done)
                for _ in range(chunk):
                    d = solve(vals, r)
                    x = masked_correction(x, d, berr, tol)
                    iters = iters + (berr > tol)
                    r, berr = res_fn(a_rows, a_cols, a_vals, a_abs, x, b,
                                     n=n)
                    # the solve's, then correction, compare, count, residual
                    n_disp += self.last_n_dispatches + 4
                done += chunk
                with span("glu.sync"):
                    berr_h, iters_h = jax.device_get((berr, iters))
                syncs += 1
                if np.all(berr_h <= tol):
                    break
            if berr_h is None:                      # max_iter == 0
                with span("glu.sync"):
                    berr_h, iters_h = jax.device_get((berr, iters))
                syncs += 1
            self.last_n_dispatches = n_disp
            if kind == "single":
                berr_out = float(berr_h)
                info = {"refine_iters": int(iters_h),
                        "backward_error": berr_out,
                        "converged": berr_out <= tol,
                        "host_syncs": syncs}
            else:
                berr_out = np.asarray(berr_h)
                info = {"refine_iters": np.asarray(iters_h, dtype=np.int64),
                        "backward_error": berr_out,
                        "converged": berr_out <= tol,
                        "host_syncs": syncs}
            return x, info

    def solve_refined(self, vals, b, a_rows, a_cols, a_vals, a_abs,
                      max_iter: int, tol: float, rhs_pattern=None,
                      sync_every: int = 2):
        """Solve then refine: up to ``max_iter`` sweeps of
        ``x += solve(b - A x)`` on the existing factors, stopping when the
        componentwise backward error drops to ``tol``.  ``a_rows``/
        ``a_cols``/``a_vals`` describe A (the matrix the factors came
        from) in COO entry order; ``a_abs`` is ``|a_vals|``.  Returns
        ``(x, info)`` with ``refine_iters``, ``backward_error``,
        ``converged``, ``host_syncs``."""
        return self._solve_refined_impl(
            "single", vals, b, a_rows, a_cols, a_vals, a_abs,
            max_iter, tol, rhs_pattern, sync_every)

    def solve_refined_batched(self, vals, b, a_rows, a_cols, a_vals, a_abs,
                              max_iter: int, tol: float, rhs_pattern=None,
                              sync_every: int = 2):
        """Batched twin of :meth:`solve_refined`: one lockstep sweep per
        round, corrections masked onto the still-unconverged rows, until
        every matrix meets ``tol`` or ``max_iter`` is reached.  Info fields
        are (B,) arrays."""
        return self._solve_refined_impl(
            "batched", vals, b, a_rows, a_cols, a_vals, a_abs,
            max_iter, tol, rhs_pattern, sync_every)

    def solve_refined_multi(self, vals, b, a_rows, a_cols, a_vals, a_abs,
                            max_iter: int, tol: float, rhs_pattern=None,
                            sync_every: int = 2):
        """Many-RHS twin: (nnz,) values, (K, n) right-hand sides, shared
        factors; info fields are (K,) arrays."""
        return self._solve_refined_impl(
            "multi", vals, b, a_rows, a_cols, a_vals, a_abs,
            max_iter, tol, rhs_pattern, sync_every)
