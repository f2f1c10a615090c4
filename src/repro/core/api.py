"""GLU facade: the paper's full flow (Fig. 5) behind one class.

  A -> MC64 (max-product matching + Dr/Dc scaling) -> fill-reducing
  ordering -> symbolic fill-in -> relaxed dependency detection +
  levelization -> plan -> (re)factorize on device -> triangular solve
  (+ optional batched iterative refinement)

All host-side preprocessing lives in the planner subsystem
(:mod:`repro.core.planner`): construction asks it for a
:class:`~repro.core.planner.SymbolicPlan` — by default through the
process-wide content-addressed plan cache, so re-constructing on a pattern
that was already analyzed (a Newton re-scaling rebuild, a sweep corner, a
repeated benchmark) performs zero symbolic work (``plan_from_cache`` reports
which path was taken).  ``GLU.from_plan`` consumes a prebuilt plan directly;
``factorize``/``solve`` are the fast repeated path (SPICE Newton iterations
reuse the plan).

Permutation algebra: with row_map/col_map (old -> new),
``A_perm[row_map[i], col_map[j]] = A[i, j]`` and solving ``A x = b`` becomes
``A_perm x_perm = b_perm`` with ``b_perm = b[inv_row_map]`` and
``x = x_perm[col_map]``.

Scaling algebra: the device actually factorizes ``B = Dr A Dc`` (every
scaled entry <= 1 in magnitude, matched diagonal exactly 1 — the Duff-Koster
guarantee no-pivot LU relies on).  ``A x = b`` becomes ``B y = Dr b`` with
``x = Dc y``; both transforms are diagonal and exact to one rounding each.
The componentwise backward error max_i |r_i| / (|A||x| + |b|)_i is invariant
under both row and column scaling, so the refinement stopping test on the
scaled system is the same test on the original one.
"""
from __future__ import annotations

import warnings
from typing import Optional

import numpy as np

import jax.numpy as jnp

from ..distributed.scenario import make_scenario_sharding
from ..kernels.backend import on_tpu
from ..sparse.csc import CSC
from ..sparse.layout import resolve_layout, unpack_planes
from ..spans import span, timed
from .factorize import JaxFactorizer
from .planner import (
    MC64Scaling,
    SymbolicPlan,
    compute_scaling,
    plan_factorization,
)
from .triangular import JaxTriangularSolver

__all__ = ["GLU", "resolve_value_dtype"]

# the stages of GLU construction that ``GLU.setup_seconds`` times
SETUP_STAGES = ("scaling", "plan", "executor", "verify")


def resolve_value_dtype(dtype) -> np.dtype:
    """Resolve the *effective* value dtype JAX will actually use.

    Without 64-bit mode (``JAX_ENABLE_X64`` / ``jax.config.update
    ("jax_enable_x64", True)``) JAX silently truncates float64 -> float32
    and complex128 -> complex64.  Silent truncation on the solve path is a
    correctness bug (observed: residual 4.5e-7 on a float64 request), so a
    truncated request raises instead of warning-and-degrading.

    On a TPU backend complex128 is refused as well: the TPU compiler has no
    c128 type and aborts the whole process on one, so the request fails
    here, before anything is traced.
    """
    requested = np.dtype(dtype)
    if requested == np.dtype(np.complex128) and on_tpu():
        raise ValueError(
            "complex128 is not supported on a TPU backend (the TPU compiler "
            "has no c128 type); request dtype=complex64")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        effective = jnp.empty(0, dtype=dtype).dtype
    if np.dtype(effective) != requested:
        raise ValueError(
            f"requested value dtype {requested} would be silently truncated "
            f"to {effective} because JAX 64-bit mode is disabled; set "
            f"JAX_ENABLE_X64=1 (or jax.config.update('jax_enable_x64', "
            f"True)) before importing jax, or request dtype={effective} "
            f"explicitly")
    return requested


class GLU:
    def __init__(
        self,
        A: CSC,
        ordering: str = "auto",
        symbolic: str = "auto",
        dtype=jnp.float64,
        mc64="scale",
        fuse_levels: bool = True,
        fuse_buckets: bool = True,
        bucket_waste: float = 4.0,
        jit_schedule: bool = True,
        executable_cache="default",
        use_pallas: bool = False,
        panel_threshold: int = 16,
        static_pivot: Optional[float] = None,
        refine: int = 0,
        refine_tol: Optional[float] = None,
        dense_tail: bool = True,
        dense_tail_density: float = 0.25,
        mode_override: Optional[str] = None,
        interpret: Optional[bool] = None,
        plan_cache="default",
        layout: str = "auto",
        mesh=None,
        verify: str = "off",
    ):
        """``mc64``: ``"scale"``/``True`` — full Duff-Koster max-product
        matching with Dr/Dc scalings; ``"structural"`` — zero-free diagonal
        only (no scaling); ``"none"``/``False`` — identity.

        ``static_pivot``: relative threshold eps for the SuperLU_DIST-style
        pivot guard — any |diag| < eps * max|A| is bumped instead of
        producing inf/NaN (None disables).

        ``refine``: default number of iterative-refinement steps applied by
        ``solve``/``solve_batched`` (overridable per call); ``refine_tol``
        is the componentwise-backward-error stopping test (default 4 ulp of
        the value dtype).

        ``plan_cache``: where symbolic plans are looked up / stored —
        ``"default"`` (the process-wide content-addressed cache), a
        :class:`~repro.core.planner.PlanCache`, or ``None`` to always
        rebuild.  ``plan_from_cache`` reports whether construction reused a
        cached plan (and therefore did zero symbolic work).

        ``jit_schedule``/``executable_cache``: the whole-schedule executors —
        one jitted program per (plan digest, executor config), cached
        process-wide so a second GLU on the same plan compiles nothing; a
        (re)factorization or triangular solve is then ONE device dispatch
        (``solve_info["n_dispatches"]`` / ``["solve_dispatches"]``).
        ``fuse_buckets``/``bucket_waste`` control the bucketed ragged level
        fusion feeding those programs.

        ``dense_tail``: switch-to-dense is ON by default — a dense-enough
        trailing column block finishes as one blocked dense-LU group inside
        the fused program instead of hundreds of tiny scatter levels (no-op
        when no qualifying tail exists; ``dense_tail=False`` forces the
        strictly sparse schedule).

        ``use_pallas``/``interpret``: route SEGMENTED/PANEL levels and the
        dense tail through the Pallas kernels.  On a TPU they compile
        through Mosaic and need float32 storage (``dtype=float32`` or
        ``complex64``; a 64-bit request raises).  ``interpret=None``
        resolves from the platform — interpret mode exactly off the TPU;
        ``interpret=True`` on a TPU raises.

        ``layout``: device value-storage layout — ``"auto"`` (default)
        stores complex factors as split re/im planes (planar) whenever
        ``use_pallas=True``, which keeps the Pallas SEGMENTED/PANEL/
        dense-tail kernels in play for complex dtypes (they take no complex
        operands); without ``use_pallas`` auto stays ``"native"``, the
        faster flat-XLA lowering.  ``"native"``/``"planar"`` force either
        path.  The public interface (``solve``, ``factorized_values``,
        refinement) always speaks native complex regardless.

        ``mesh``: a ``jax.sharding.Mesh`` to shard BATCHED factorize/solve
        calls over — the batch (scenario) axis splits along the mesh axes
        the ``"scenario"`` rule of ``repro.distributed.DEFAULT_RULES``
        resolves to (``("pod", "data")``), plan metadata is replicated, and
        each shard runs the whole fused schedule in its single dispatch.
        Batches not divisible by the shard count are padded with copies of
        the last scenario and the pad rows are masked out of results and
        diagnostics.  ``None`` (default) or a mesh resolving to one shard
        runs everything on the default device.  Single-matrix calls are
        never sharded.

        ``verify``: static plan verification (:mod:`repro.analysis`).
        ``"off"`` (default) — none, zero overhead; ``"plan"`` — verify the
        symbolic plan's schedule/index invariants at construction;
        ``"full"`` — additionally walk the built executor and trisolver
        schedules and audit the fused runners' jaxprs.  Violations raise
        :class:`~repro.analysis.PlanVerificationError`; the report summary
        lands in ``solve_info["verify_report"]``.

        ``setup_seconds`` records construction's host seconds by stage:
        ``"scaling"`` (MC64 and the plan key), ``"plan"`` (the symbolic
        plan build, 0 on a plan-cache hit), ``"executor"`` (factorizer and
        triangular-solver build, plan arrays moved to the device) and
        ``"verify"`` (0 with ``verify="off"``).  Each stage is also a
        profiler span (``glu.scaling``, ``glu.plan``,
        ``glu.executor_build``, ``glu.verify``) inside ``glu.setup``.
        """
        self.setup_seconds = dict.fromkeys(SETUP_STAGES, 0.0)
        with span("glu.setup"):
            plan, scaling, from_cache = plan_factorization(
                A, ordering=ordering, symbolic=symbolic, mc64=mc64,
                panel_threshold=panel_threshold, cache=plan_cache,
                seconds=self.setup_seconds)
            self._setup(
                plan, scaling, A, from_cache=from_cache, dtype=dtype,
                fuse_levels=fuse_levels, fuse_buckets=fuse_buckets,
                bucket_waste=bucket_waste, jit_schedule=jit_schedule,
                executable_cache=executable_cache, use_pallas=use_pallas,
                static_pivot=static_pivot, refine=refine,
                refine_tol=refine_tol, dense_tail=dense_tail,
                dense_tail_density=dense_tail_density,
                mode_override=mode_override, interpret=interpret,
                layout=layout, mesh=mesh, verify=verify)

    @classmethod
    def from_plan(
        cls,
        plan: SymbolicPlan,
        A: CSC,
        dtype=jnp.float64,
        mc64="scale",
        fuse_levels: bool = True,
        fuse_buckets: bool = True,
        bucket_waste: float = 4.0,
        jit_schedule: bool = True,
        executable_cache="default",
        use_pallas: bool = False,
        static_pivot: Optional[float] = None,
        refine: int = 0,
        refine_tol: Optional[float] = None,
        dense_tail: bool = True,
        dense_tail_density: float = 0.25,
        mode_override: Optional[str] = None,
        interpret: Optional[bool] = None,
        layout: str = "auto",
        mesh=None,
        verify: str = "off",
    ) -> "GLU":
        """Build a GLU around a prebuilt :class:`SymbolicPlan`, skipping all
        symbolic work.

        ``A`` must carry the exact pattern the plan was built for, and the
        MC64 matching of its values must reproduce ``plan.row_perm`` (for
        ``mc64="scale"`` the matching is recomputed from the new values —
        only the resulting permutation has to agree; the Dr/Dc scalings are
        free to differ).  Raises ``ValueError`` otherwise.
        """
        if not plan.matches_pattern(A):
            raise ValueError("matrix pattern differs from the plan's pattern")
        self = cls.__new__(cls)
        self.setup_seconds = dict.fromkeys(SETUP_STAGES, 0.0)
        with span("glu.setup"):
            with timed("glu.scaling", self.setup_seconds, "scaling"):
                scaling = compute_scaling(A, mc64)
            if not np.array_equal(scaling.row_perm, plan.row_perm):
                raise ValueError(
                    "MC64 matching of these values differs from the plan's "
                    "row permutation; rebuild the plan (e.g. GLU(A, ...))")
            self._setup(
                plan, scaling, A, from_cache=True, dtype=dtype,
                fuse_levels=fuse_levels, fuse_buckets=fuse_buckets,
                bucket_waste=bucket_waste, jit_schedule=jit_schedule,
                executable_cache=executable_cache, use_pallas=use_pallas,
                static_pivot=static_pivot, refine=refine,
                refine_tol=refine_tol, dense_tail=dense_tail,
                dense_tail_density=dense_tail_density,
                mode_override=mode_override, interpret=interpret,
                layout=layout, mesh=mesh, verify=verify)
        return self

    def _setup(
        self,
        plan: SymbolicPlan,
        scaling: MC64Scaling,
        A: CSC,
        from_cache: bool,
        dtype,
        fuse_levels: bool,
        fuse_buckets: bool,
        bucket_waste: float,
        jit_schedule: bool,
        executable_cache,
        use_pallas: bool,
        static_pivot: Optional[float],
        refine: int,
        refine_tol: Optional[float],
        dense_tail: bool,
        dense_tail_density: float,
        mode_override: Optional[str],
        interpret: Optional[bool],
        layout: str,
        mesh=None,
        verify: str = "off",
    ) -> None:
        # resolve the effective dtype ONCE; a float64/complex128 request
        # without x64 enabled raises here instead of silently degrading
        dtype = resolve_value_dtype(dtype)
        # "auto" picks planar exactly when it buys something: complex dtype
        # AND mode-adaptive Pallas execution requested.  Without use_pallas
        # every level runs flat XLA, where native complex (an interleaved
        # re/im layout already) is the faster lowering — planar would only
        # add plane bookkeeping.  Pass layout="planar" to force planes.
        if layout == "auto" and not use_pallas:
            layout = "native"
        self.layout = resolve_layout(layout, dtype)
        self.n = A.n
        self.symbolic_plan = plan
        self.plan_from_cache = bool(from_cache)
        self._A_scipy = A.to_scipy()
        rows0 = np.asarray(A.indices, dtype=np.int64)
        cols0 = np.repeat(np.arange(A.n, dtype=np.int64), np.diff(A.indptr))
        self.Dr, self.Dc = scaling.Dr, scaling.Dc
        # per-original-entry scale factor: entry (i, j) -> Dr[i] * Dc[j];
        # identity for the unscaled modes, where the multiply is skipped
        self._scale_data = self.Dr[rows0] * self.Dc[cols0]
        self._scale_identity = bool(np.all(self._scale_data == 1.0))
        self.row_map = plan.row_map             # old row -> new row
        self.col_map = plan.col_map             # old col -> new col
        self._inv_row = plan.inv_row
        # original-entry-order -> permuted-entry-order map (for refactorize)
        self._data_perm = plan.data_perm
        # no float64 hard-cast: A.data may be complex (AC analysis); the
        # real Dr/Dc scale factors preserve the value dtype kind
        scaled = np.asarray(A.data) * self._scale_data
        self._A_perm = CSC(A.n, plan.perm_indptr, plan.perm_indices,
                           scaled[self._data_perm])
        self.pattern = plan.pattern
        self.levelization = plan.levelization
        self.plan = plan.fplan
        # scenario sharding: None unless a mesh with >1 scenario shards was
        # given; sharding only ever applies to the batched entry points
        self.mesh = mesh
        self._shard = make_scenario_sharding(mesh)
        with timed("glu.executor_build", self.setup_seconds, "executor"):
            # scaled-A SpMV layout (permuted pattern) for iterative refinement
            self._spmv_rows = jnp.asarray(plan.spmv_rows)
            self._spmv_cols = jnp.asarray(plan.spmv_cols)
            self._factorizer = JaxFactorizer(
                self.plan, dtype=dtype, fuse_levels=fuse_levels,
                fuse_buckets=fuse_buckets, bucket_waste=bucket_waste,
                jit_schedule=jit_schedule, executable_cache=executable_cache,
                use_pallas=use_pallas, mode_override=mode_override,
                interpret=interpret, dense_tail=dense_tail,
                dense_tail_density=dense_tail_density,
                static_pivot=static_pivot, layout=self.layout.name,
                shard=self._shard,
            )
            self._solver = JaxTriangularSolver(
                self.plan, fuse=fuse_levels, fuse_buckets=fuse_buckets,
                bucket_waste=bucket_waste, jit_schedule=jit_schedule,
                executable_cache=executable_cache, layout=self.layout.name,
                shard=self._shard,
                dense_tail=self._factorizer.dense_tail_info)
        self._vals: Optional[jnp.ndarray] = None
        self._vals_batch: Optional[jnp.ndarray] = None
        self._a_vals: Optional[jnp.ndarray] = None
        self._a_abs: Optional[jnp.ndarray] = None
        self._a_vals_batch: Optional[jnp.ndarray] = None
        self._a_abs_batch: Optional[jnp.ndarray] = None
        # batch geometry of the current batched factorization: the caller's
        # B, the padded total held on device, and their difference
        self._batch_size: Optional[int] = None
        self._batch_total: Optional[int] = None
        self._batch_pad: int = 0
        self.dtype = dtype
        self.refine_default = int(refine)
        self.refine_tol = (float(refine_tol) if refine_tol is not None
                           else 4.0 * float(jnp.finfo(dtype).eps))
        self._info: Optional[dict] = None
        self._pending_stats = None
        if verify not in ("off", "plan", "full"):
            raise ValueError(
                f"verify must be 'off', 'plan' or 'full', got {verify!r}")
        self.verify = verify
        self.verify_report = None
        if verify != "off":
            # lazy import: analysis depends on core, not the other way round
            from ..analysis import verify_glu

            with timed("glu.verify", self.setup_seconds, "verify"):
                self.verify_report = verify_glu(self, verify)
            self.verify_report.raise_if_violated()

    # -- numeric phase (repeatable) -----------------------------------------
    def factorize(self, a_data=None) -> "GLU":
        """(Re)factorize; ``a_data`` are new values in A's original CSC entry
        order (same pattern — the SPICE refactorization contract).  The
        batched factor cache is invalidated: the two caches can never refer
        to different matrix values."""
        with span("glu.factorize"):
            with span("glu.prep"):
                if a_data is None:
                    data = np.asarray(self._A_perm.data)
                elif self._scale_identity:
                    data = np.asarray(a_data)[self._data_perm]
                else:
                    data = ((np.asarray(a_data) * self._scale_data)
                            [self._data_perm])
            with span("glu.h2d"):
                self._a_vals = jnp.asarray(data, dtype=self.dtype)
            self._a_abs = None                 # lazily built on refined solve
            self._vals = self._factorizer.factorize(self._a_vals)
            self._vals_batch = None
            self._a_vals_batch = None
            self._a_abs_batch = None
            self._batch_size = self._batch_total = None
            self._batch_pad = 0
            self._set_fact_info(self._vals, self._a_vals, batched=False)
        return self

    def factorized_values(self) -> jnp.ndarray:
        """Factored (nnz,) values in the plan's filled pattern — always in
        the NATIVE value dtype (planar plane storage is unpacked here; use
        ``_vals`` for the raw device layout)."""
        if self._vals is None:
            raise RuntimeError("call factorize() first")
        if self.layout.planar:
            return unpack_planes(self._vals)
        return self._vals

    def _map_rhs_pattern(self, rhs_pattern, b) -> Optional[np.ndarray]:
        """Translate a rhs nonzero pattern from ORIGINAL row indices to the
        solver's permuted positions, validating that ``b`` really is zero
        outside the pattern (a nonzero outside it would be silently
        dropped by the pruned schedule)."""
        if rhs_pattern is None:
            return None
        pat = np.unique(np.asarray(rhs_pattern, dtype=np.int64).ravel())
        if pat.size and (pat[0] < 0 or pat[-1] >= self.n):
            raise ValueError(f"rhs_pattern indices out of range [0, {self.n})")
        mask = np.zeros(self.n, dtype=bool)
        mask[pat] = True
        bad = np.asarray(b) != 0
        if bad.ndim == 2:
            bad = bad.any(axis=0)
        if np.any(bad & ~mask):
            raise ValueError(
                "rhs has nonzero entries outside rhs_pattern; the pruned "
                "solve would silently drop them")
        return self.row_map[pat]

    def solve(self, b, refine: Optional[int] = None,
              rhs_pattern=None) -> np.ndarray:
        """Solve A x = b using the current factorization; ``refine`` extra
        iterative-refinement sweeps reuse the device factors (default: the
        constructor's ``refine``).  ``rhs_pattern`` — indices (original row
        numbering) of b's nonzero support — prunes the triangular-solve
        schedule to the reach closure of the pattern (raises if b is
        nonzero outside it)."""
        with span("glu.solve"):
            if self._vals is None:
                if self._vals_batch is not None:
                    raise RuntimeError(
                        "the active factorization is batched — use "
                        "solve_batched(), or call factorize() to refactorize "
                        "single-matrix first")
                self.factorize()
            k = self.refine_default if refine is None else int(refine)
            with span("glu.prep"):
                pat = self._map_rhs_pattern(rhs_pattern, b)
                bp = (np.asarray(b) * self.Dr)[self._inv_row]
            launched = 0          # device programs besides the solver's
            if k > 0 and self._a_abs is None:
                self._a_abs = jnp.abs(self._a_vals)
                launched = 1
            with span("glu.h2d"):
                # refinement runs in the value dtype; a plain solve's
                # program casts the rhs itself
                bpd = jnp.asarray(bp, dtype=self.dtype if k > 0 else None)
            if k > 0:
                xp, rinfo = self._solver.solve_refined(
                    self._vals, bpd, self._spmv_rows, self._spmv_cols,
                    self._a_vals, self._a_abs, max_iter=k,
                    tol=self.refine_tol, rhs_pattern=pat)
            else:
                xp = self._solver.solve(self._vals, bpd, rhs_pattern=pat)
                rinfo = {"refine_iters": 0, "backward_error": None,
                         "converged": None, "host_syncs": 0}
            with span("glu.d2h"):
                xp = np.asarray(xp)
            self._set_solve_info(rinfo, launched)
            with span("glu.post"):
                return xp[self.col_map] * self.Dc

    def solve_multi(self, b_multi, refine: Optional[int] = None,
                    rhs_pattern=None) -> np.ndarray:
        """Solve A X^T = B^T — many right-hand sides against the CURRENT
        single-matrix factorization (the adjoint/sensitivity workload:
        K seed vectors, one Jacobian).  ``b_multi`` is (K, n), returns
        (K, n); each level group is one device dispatch for all K rhs.
        ``rhs_pattern`` is the union support of all rows."""
        with span("glu.solve_multi"):
            if self._vals is None:
                if self._vals_batch is not None:
                    raise RuntimeError(
                        "the active factorization is batched — use "
                        "solve_batched(), or call factorize() to refactorize "
                        "single-matrix first")
                self.factorize()
            b = np.asarray(b_multi)
            if b.ndim != 2 or b.shape[1] != self.n:
                raise ValueError(f"expected (K, {self.n}) rhs, got {b.shape}")
            k = self.refine_default if refine is None else int(refine)
            with span("glu.prep"):
                pat = self._map_rhs_pattern(rhs_pattern, b)
                bp = (b * self.Dr[None, :])[:, self._inv_row]
            launched = 0          # device programs besides the solver's
            if k > 0 and self._a_abs is None:
                self._a_abs = jnp.abs(self._a_vals)
                launched = 1
            with span("glu.h2d"):
                # refinement runs in the value dtype; a plain solve's
                # program casts the rhs itself
                bpd = jnp.asarray(bp, dtype=self.dtype if k > 0 else None)
            if k > 0:
                xp, rinfo = self._solver.solve_refined_multi(
                    self._vals, bpd, self._spmv_rows, self._spmv_cols,
                    self._a_vals, self._a_abs, max_iter=k,
                    tol=self.refine_tol, rhs_pattern=pat)
            else:
                xp = self._solver.solve_multi(self._vals, bpd,
                                              rhs_pattern=pat)
                rinfo = {"refine_iters": np.zeros(b.shape[0], dtype=np.int64),
                         "backward_error": None, "converged": None,
                         "host_syncs": 0}
            with span("glu.d2h"):
                xp = np.asarray(xp)
            self._set_solve_info(rinfo, launched)
            with span("glu.post"):
                return xp[:, self.col_map] * self.Dc[None, :]

    # -- batched numeric phase (one plan, many matrices) ----------------------
    def factorize_batched(self, a_data_batch) -> "GLU":
        """Factorize B matrices on this pattern in lockstep.

        ``a_data_batch``: (B, nnz) values, one matrix per row, each in A's
        original CSC entry order (the Monte-Carlo / parameter-sweep
        refactorization contract: one symbolic plan, many value vectors).
        The single-matrix factor cache is invalidated."""
        with span("glu.factorize_batched"):
            with span("glu.prep"):
                data = np.asarray(a_data_batch)
                if data.ndim != 2:
                    raise ValueError(
                        f"expected (B, nnz) values, got shape {data.shape}")
                if self._scale_identity:
                    scaled = data[:, self._data_perm]
                else:
                    scaled = ((data * self._scale_data[None, :])
                              [:, self._data_perm])
                B = scaled.shape[0]
                self._batch_size = self._batch_total = B
                self._batch_pad = 0
                if self._shard is not None and B > 1:
                    # non-divisible batches are padded with copies of the
                    # LAST scenario (a known-factorizable system, so the pad
                    # rows can never poison diagnostics with inf/NaN) and
                    # masked out of results and convergence below — the
                    # scenario-axis analogue of the silent-replicate rule in
                    # distributed/sharding.py.  B == 1 stays unsharded:
                    # padding a single matrix across the mesh buys nothing.
                    total = self._shard.pad(B)
                    if total != B:
                        scaled = np.concatenate(
                            [scaled,
                             np.repeat(scaled[-1:], total - B, axis=0)])
                    self._batch_total = total
                    self._batch_pad = total - B
            with span("glu.h2d"):
                self._a_vals_batch = jnp.asarray(scaled, dtype=self.dtype)
                if (self._shard is not None
                        and self._batch_total % self._shard.n_shards == 0):
                    # place the batch sharded BEFORE dispatch so the runner
                    # never reshuffles it (donation-safe: the runner does
                    # not donate it)
                    self._a_vals_batch = self._shard.shard_batch(
                        self._a_vals_batch)
            self._a_abs_batch = None           # lazily built on refined solve
            self._vals_batch = self._factorizer.factorize_batched(
                self._a_vals_batch)
            self._vals = None
            self._a_vals = None
            self._a_abs = None
            self._set_fact_info(self._vals_batch, self._a_vals_batch,
                                batched=True)
        return self

    def factorized_values_batched(self) -> jnp.ndarray:
        if self._vals_batch is None:
            raise RuntimeError("call factorize_batched() first")
        vals = self._vals_batch
        if self._batch_pad:
            vals = vals[: self._batch_size]
        if self.layout.planar:
            return unpack_planes(vals)
        return vals

    def solve_batched(self, b_batch, refine: Optional[int] = None,
                      rhs_pattern=None) -> np.ndarray:
        """Solve A_i x_i = b_i for every matrix of the current batched
        factorization; ``b_batch`` is (B, n), returns (B, n).  A
        ``rhs_pattern`` is shared by the batch (union support)."""
        with span("glu.solve_batched"):
            if self._vals_batch is None:
                raise RuntimeError("call factorize_batched() first")
            B = np.asarray(b_batch).shape[0]
            if self._batch_size is not None and B != self._batch_size:
                raise ValueError(
                    f"rhs batch of {B} does not match the factorized batch "
                    f"of {self._batch_size}")
            k = self.refine_default if refine is None else int(refine)
            with span("glu.prep"):
                pat = self._map_rhs_pattern(rhs_pattern, np.asarray(b_batch))
                bp = (np.asarray(b_batch) * self.Dr[None, :])[:, self._inv_row]
                if self._batch_pad:
                    # zero rhs rows for the pad scenarios: their solution is
                    # exactly zero (and their backward error 0/0 counts as
                    # converged), so refinement never iterates for them
                    bp = np.concatenate(
                        [bp, np.zeros((self._batch_pad, bp.shape[1]),
                                      dtype=bp.dtype)])
            with span("glu.h2d"):
                bpd = jnp.asarray(bp)
                if (self._shard is not None
                        and bpd.shape[0] % self._shard.n_shards == 0):
                    bpd = self._shard.shard_batch(bpd)
            launched = 0          # device programs besides the solver's
            if k > 0 and self._a_abs_batch is None:
                self._a_abs_batch = jnp.abs(self._a_vals_batch)
                launched = 1
            if k > 0:
                xp, rinfo = self._solver.solve_refined_batched(
                    self._vals_batch, bpd, self._spmv_rows, self._spmv_cols,
                    self._a_vals_batch, self._a_abs_batch,
                    max_iter=k, tol=self.refine_tol, rhs_pattern=pat)
            else:
                xp = self._solver.solve_batched(self._vals_batch, bpd,
                                                rhs_pattern=pat)
                rinfo = {"refine_iters": np.zeros(B, dtype=np.int64),
                         "backward_error": None, "converged": None,
                         "host_syncs": 0}
            with span("glu.d2h"):
                xp = np.asarray(xp)
            with span("glu.post"):
                if self._batch_pad:
                    xp = xp[:B]
                    rinfo = {key: (v[:B] if isinstance(v, np.ndarray) else v)
                             for key, v in rinfo.items()}
                x = xp[:, self.col_map] * self.Dc[None, :]
            self._set_solve_info(rinfo, launched)
            return x

    def refactorize_solve(self, a_data_batch, b_batch,
                          refine: Optional[int] = None,
                          rhs_pattern=None) -> np.ndarray:
        """Fused batched refactorize + solve in one call (the Newton inner
        step of a parameter sweep).  Accepts (B, nnz)+(B, n) or a single
        (nnz,)+(n,) pair; the factored values stay on device between the
        two phases and are kept for later ``solve_batched`` calls."""
        with span("glu.refactorize_solve"):
            data = np.asarray(a_data_batch)
            b = np.asarray(b_batch)
            single = data.ndim == 1
            if single:
                data, b = data[None], b[None]
            self.factorize_batched(data)
            x = self.solve_batched(b, refine=refine, rhs_pattern=rhs_pattern)
            if not single:
                return x
            self._vals = self._vals_batch[0]
            self._a_vals = self._a_vals_batch[0]
            self._a_abs = (None if self._a_abs_batch is None
                           else self._a_abs_batch[0])
            # collapse diagnostics to the documented single-matrix contract
            # (scalars, batched=False), matching the returned x[0]
            if self._pending_stats is not None:
                _, _, a_max, n_pert, _ = self._pending_stats
                self._pending_stats = (
                    self._vals, self._a_vals,
                    None if a_max is None else a_max[0],
                    None if n_pert is None else n_pert[0], False)
            if self._info is not None:
                self._info["batched"] = False
                for key in ("pivot_growth", "min_diag", "n_perturbed",
                            "refine_iters", "backward_error", "converged"):
                    v = self._info.get(key)
                    if v is not None and not isinstance(v, (bool, int, float)):
                        self._info[key] = np.asarray(v)[0]
            return x[0]

    # -- diagnostics ----------------------------------------------------------
    def _set_fact_info(self, factored_vals, a_vals, batched: bool) -> None:
        """Record which factorization the next ``solve_info`` describes.
        The growth/min-diag reductions (and max|A| when the static-pivot
        guard didn't already need it) are deferred to first ``solve_info``
        access so the hot refactorization path pays nothing for them."""
        self._pending_stats = (factored_vals, a_vals,
                               self._factorizer.last_a_max,
                               self._factorizer.last_n_perturbed,
                               batched)
        sharded = (batched and self._shard is not None
                   and self._batch_total is not None
                   and self._batch_total % self._shard.n_shards == 0)
        self._info = {
            "batched": batched,
            "pivot_growth": None,
            "min_diag": None,
            "n_perturbed": None,
            "refine_iters": None,
            "backward_error": None,
            "converged": None,
            # executor shape: how many schedule groups the plan compiled to
            # and how many device dispatches this factorization actually
            # issued (1 on the fused whole-schedule path)
            "n_groups": self._factorizer.n_groups,
            "n_dispatches": self._factorizer.last_n_dispatches,
            "solve_dispatches": None,
            **self._factorize_counters(),
            # the latest trisolve's dense-tail size (0: walked by levels)
            # and its padded gather/scatter entries, forward and backward
            "trisolve_dense_tail": None,
            "trisolve_indexed_entries": None,
            # mode-adaptive execution surface: which storage layout the
            # factors use, and — when any Pallas-eligible work was routed
            # off the Pallas path — why (None means fully active)
            "layout": self.layout.name,
            "pallas_disabled_reason": self._factorizer.pallas_disabled_reason,
            # scenario-sharding surface: how many devices the batch axis
            # split over (1 = unsharded) and the PartitionSpec it used.
            # ``n_perturbed_global`` is the cross-shard exact psum of
            # static-pivot bumps over the PADDED batch (pad rows duplicate
            # the last scenario, so their bumps are counted again); None
            # unless the guard ran sharded.
            "n_devices": self._shard.n_shards if sharded else 1,
            "batch_spec": str(self._shard.spec) if sharded else None,
            "n_perturbed_global": self._factorizer.last_n_perturbed_global,
            # static-verification digest (None when verify="off")
            "verify_report": (None if self.verify_report is None
                              else self.verify_report.summary()),
        }

    def _factorize_counters(self) -> dict:
        """What one factorize walks, set on the host from the built groups:
        sparse levels before the dense block, their padded scatter-add
        entries, and the dense block's columns (0 if none)."""
        f = self._factorizer
        tail = f.dense_tail_info
        return {"factorize_levels": f.sparse_levels,
                "factorize_update_entries": f.update_entries,
                "factorize_dense_tail": 0 if tail is None else tail["size"]}

    def _set_solve_info(self, rinfo: dict, launched: int) -> None:
        """Record the latest solve; ``launched`` counts the device programs
        the facade launched around the solver's own (the lazy |A|)."""
        if self._info is None:
            self._info = {"batched": False, "pivot_growth": None,
                          "min_diag": None, "n_perturbed": None,
                          "n_groups": self._factorizer.n_groups,
                          "n_dispatches": None,
                          **self._factorize_counters(),
                          "layout": self.layout.name,
                          "pallas_disabled_reason":
                              self._factorizer.pallas_disabled_reason,
                          "n_devices": 1, "batch_spec": None,
                          "n_perturbed_global": None,
                          "verify_report": (
                              None if self.verify_report is None
                              else self.verify_report.summary())}
        self._info.update(rinfo)
        self._info["solve_dispatches"] = (self._solver.last_n_dispatches
                                          + launched)
        self._info["trisolve_dense_tail"] = self._solver.last_dense_tail
        self._info["trisolve_indexed_entries"] = (
            self._solver.last_indexed_entries)

    @property
    def refine_converged(self):
        """Convergence flag (and nothing else) of the latest refined solve:
        scalar bool / (B,) bool array, or None when the last solve ran
        unrefined.  Unlike ``solve_info`` it does not force the deferred
        pivot-growth/min-diag device reductions, so the Newton hot loop can
        poll it every iterate for free."""
        if self._info is None:
            return None
        v = self._info.get("converged")
        if v is None or isinstance(v, bool):
            return v
        a = np.asarray(v)
        return bool(a.item()) if a.ndim == 0 else a

    @property
    def solve_info(self) -> Optional[dict]:
        """Robustness report of the latest factorize/solve: ``pivot_growth``
        (max|LU|/max|A|), ``min_diag``, ``n_perturbed`` (static-pivot bumps;
        None when the guard is off), ``refine_iters``, ``backward_error``
        (componentwise), ``converged``, and ``batched``.  Scalars for the
        single-matrix path, (B,) arrays for the batched one."""
        if self._info is None:
            return None
        if self._pending_stats is not None:
            from ..kernels import ops as kops

            vals, a_vals, a_max, n_pert, batched = self._pending_stats
            if a_max is None:
                a_abs = jnp.abs(a_vals)
                a_max = jnp.max(a_abs, axis=1) if batched else jnp.max(a_abs)
            if self.layout.planar:
                fn = (kops.factor_stats_planar_batched if batched
                      else kops.factor_stats_planar)
            else:
                fn = (kops.factor_stats_batched if batched
                      else kops.factor_stats)
            growth, min_diag = fn(vals, self._factorizer._diag_idx, a_max)
            if batched and self._batch_pad:
                # drop the pad scenarios from the per-matrix diagnostics
                growth = growth[: self._batch_size]
                min_diag = min_diag[: self._batch_size]
                if n_pert is not None:
                    n_pert = n_pert[: self._batch_size]
            self._info.update(pivot_growth=growth, min_diag=min_diag,
                              n_perturbed=n_pert)
            self._pending_stats = None
        out = {}
        for key, v in self._info.items():
            if v is None or isinstance(v, (bool, int, float, str, dict)):
                out[key] = v
            else:
                a = np.asarray(v)
                out[key] = a.item() if a.ndim == 0 else a
        return out

    @property
    def n_devices(self) -> int:
        """Shard count batched calls split over (1 = unsharded)."""
        return 1 if self._shard is None else self._shard.n_shards

    @property
    def nnz_filled(self) -> int:
        return self.pattern.nnz

    @property
    def num_levels(self) -> int:
        return self.levelization.num_levels

    def residual(self, b, x) -> float:
        """||Ax - b||_inf / ||b||_inf on the original system."""
        r = self._A_scipy @ np.asarray(x) - np.asarray(b)
        return float(np.abs(r).max() / (np.abs(b).max() + 1e-300))
