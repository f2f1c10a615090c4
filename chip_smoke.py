#!/usr/bin/env python3
"""Bring-up smoke test: the GLU factorize/solve path on a TPU.

    python3 chip_smoke.py                # every one-chip phase
    python3 chip_smoke.py --four-chips   # only the scenario-sharded phase
                                         # and its unsharded comparison

Every phase drives the library through the entry points its users call
(``GLU``, ``refactorize_solve``, ``repro.launch.simulate``,
``transient_sweep(mesh=)``) and checks what comes out against an
independent reference: scipy ``splu`` and the componentwise backward error
computed on the host, or the same work run unsharded.  Lines starting with
``#`` are information (plan, compile and warm times, the schedule's group
counts), not measurements to compare.  The last line is one JSON object
naming the device.  A failed check, or a backend that is not a TPU, exits
non-zero before that line.

The persistent compilation cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``.jax_cache/`` of this checkout, so a second run reports
fewer compile seconds.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_ENABLE_X64", "1")
sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

MATRIX = "rajat12_like"       # paper Table I matrix, at its full suite size
REFINE = 3                    # refinement sweeps allowed per solve
BERR_F64 = 1e-12              # componentwise backward error target (f64)
SEED = 0

_COMPILE = {"seconds": 0.0, "programs": 0}


def info(msg: str) -> None:
    print(f"# {msg}", flush=True)


def check(ok, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {msg}")
    info(f"ok  {msg}")


def backward_error(S, x, b) -> float:
    """max_i |b - A x|_i / (|A| |x| + |b|)_i on the host, in float64."""
    r = b - S @ x
    denom = abs(S) @ np.abs(x) + np.abs(b)
    return float(np.max(np.abs(r) / denom))


def rel_diff(x, ref) -> float:
    return float(np.max(np.abs(x - ref)) / np.max(np.abs(ref)))


def compile_seconds() -> float:
    return _COMPILE["seconds"]


def group_counts(glu) -> dict:
    counts: dict = {}
    for g in glu._factorizer._groups:
        key = f"{g.kind}/{g.mode}"
        counts[key] = counts.get(key, 0) + 1
    return counts


def with_values(A, vals):
    """scipy CSC matrix of A's pattern holding ``vals`` in A's entry order."""
    import scipy.sparse as sp

    return sp.csc_matrix((vals, A.indices, A.indptr), shape=(A.n, A.n))


def perturbed(data, rng, shape, spread):
    """Values of the same pattern scaled by factors in [1-spread, 1+spread]."""
    return np.asarray(data)[None] * (1.0 + spread * rng.uniform(-1, 1, shape))


def phase_default(A, S, b, rng):
    """GLU(A) with the defaults: float64, XLA path, fused schedule."""
    from scipy.sparse.linalg import splu

    from repro.core import GLU

    t = time.perf_counter()
    glu = GLU(A)
    info(f"plan seconds {time.perf_counter() - t:.3f}  n={A.n} nnz(A)={A.nnz} "
         f"nnz(L+U)={glu.nnz_filled} levels={glu.num_levels}")
    info(f"f64 groups {group_counts(glu)} dense tail "
         f"{glu._factorizer.dense_tail_info}")
    c0, t = compile_seconds(), time.perf_counter()
    glu.factorize()
    x0 = glu.solve(b, refine=0)
    info(f"f64 first factorize+solve seconds {time.perf_counter() - t:.3f} "
         f"(compile seconds {compile_seconds() - c0:.3f})")
    si = glu.solve_info
    check(si["n_dispatches"] == 1 and si["solve_dispatches"] == 1,
          f"f64 factorize and solve are one dispatch each "
          f"({si['n_dispatches']}, {si['solve_dispatches']})")
    check(np.isfinite(x0).all(), "f64 unrefined solution is finite")

    x = glu.solve(b, refine=REFINE)
    e = backward_error(S, x, b)
    check(e <= BERR_F64, f"f64 backward error {e:.3e} <= {BERR_F64:g}")
    x_ref = splu(S).solve(b)
    d = rel_diff(x, x_ref)
    check(d <= 1e-9, f"f64 solution vs scipy splu: rel diff {d:.3e} <= 1e-9")

    for k in range(3):
        vals = perturbed(A.data, rng, A.nnz, 0.1)[0]
        Sk = with_values(A, vals)
        glu.factorize(vals)
        xk = glu.solve(b, refine=REFINE)
        e = backward_error(Sk, xk, b)
        check(e <= BERR_F64 and glu.solve_info["n_dispatches"] == 1,
              f"f64 refactorization {k}: backward error {e:.3e}, one dispatch")

    t = time.perf_counter()
    glu.factorize(vals)
    import jax

    jax.block_until_ready(glu.factorized_values())
    xk = glu.solve(b, refine=REFINE)
    info(f"f64 warm factorize+solve seconds {time.perf_counter() - t:.4f} "
         f"(refine<={REFINE}, iters {glu.solve_info['refine_iters']})")
    return glu, x


def phase_batched(glu, A, S, rng, B=16):
    """refactorize_solve over B matrices on the same plan."""
    vals = perturbed(A.data, rng, (B, A.nnz), 0.1)
    rhs = rng.standard_normal((B, A.n))
    c0, t = compile_seconds(), time.perf_counter()
    X = glu.refactorize_solve(vals, rhs, refine=REFINE)
    info(f"batched B={B} first refactorize_solve seconds "
         f"{time.perf_counter() - t:.3f} (compile seconds "
         f"{compile_seconds() - c0:.3f})")
    check(glu.solve_info["n_dispatches"] == 1,
          f"batched B={B} factorization is one dispatch")
    worst = 0.0
    for i in range(B):
        worst = max(worst, backward_error(with_values(A, vals[i]), X[i],
                                          rhs[i]))
    check(worst <= BERR_F64,
          f"batched B={B}: worst row backward error {worst:.3e}")
    t = time.perf_counter()
    glu.refactorize_solve(vals, rhs, refine=REFINE)
    info(f"batched B={B} warm refactorize_solve seconds "
         f"{time.perf_counter() - t:.4f}")


def phase_pallas(A, S, b, x64):
    """The paper's mode-adaptive kernels: float32, use_pallas=True."""
    import jax
    import jax.numpy as jnp

    from repro.core import GLU

    glu = GLU(A, dtype=jnp.float32, use_pallas=True)
    counts = group_counts(glu)
    info(f"f32 pallas groups {counts}")
    check(any(k in counts for k in ("pallas/segmented", "pallas/panel"))
          and "dense/dense" in counts,
          "f32 schedule has SEGMENTED/PANEL Pallas groups and a dense tail")
    fx = glu._factorizer
    a32 = jnp.asarray(np.asarray(A.data), dtype=jnp.float32)
    c0, t = compile_seconds(), time.perf_counter()
    text = fx._runner_for("scatter", False).lower(
        a32, fx._a_scatter, fx._group_arrays, fx._group_diags,
        None).compile().as_text()
    info(f"f32 pallas factorize compile seconds {compile_seconds() - c0:.3f} "
         f"(wall {time.perf_counter() - t:.3f})")
    check("tpu_custom_call" in text,
          "compiled f32 factorize program contains tpu_custom_call")

    glu.factorize()
    si = glu.solve_info
    check(si["pallas_disabled_reason"] is None and not fx.interpret,
          "Pallas kernels compiled, not interpreted "
          f"(reason {si['pallas_disabled_reason']!r})")
    x = glu.solve(b, refine=8)
    si = glu.solve_info
    check(si["converged"] and si["backward_error"] <= glu.refine_tol,
          f"f32 refined backward error {si['backward_error']:.3e} <= "
          f"refine_tol {glu.refine_tol:.3e} in {si['refine_iters']} sweeps")
    e = backward_error(S, x, b)
    info(f"f32 backward error recomputed on the host in f64: {e:.3e}")
    # forward error of a solve with componentwise backward error e is at
    # most cond(A, x) * e (Skeel); 8 * refine_tol covers the f32 residual
    # rounding on top of the device's stopping test
    cond = skeel_cond(S, x64)
    tol = cond * 8 * glu.refine_tol
    d = rel_diff(x, x64)
    check(d <= tol, f"f32 solution vs f64 phase: rel diff {d:.3e} <= "
          f"cond {cond:.3e} x 8 x refine_tol = {tol:.3e}")
    t = time.perf_counter()
    glu.factorize()
    jax.block_until_ready(glu.factorized_values())
    glu.solve(b, refine=8)
    info(f"f32 pallas warm factorize+solve seconds "
         f"{time.perf_counter() - t:.4f}")


def skeel_cond(S, x) -> float:
    """|| |A^-1| |A| |x| ||_inf / ||x||_inf (dense inverse on the host)."""
    Ainv = np.linalg.inv(S.toarray())
    return float(np.max(np.abs(Ainv) @ (abs(S) @ np.abs(x)))
                 / np.max(np.abs(x)))


def phase_transient():
    """The paper's application: backward-Euler + Newton on a 32x32 grid."""
    from repro.launch import simulate

    c0 = compile_seconds()
    res = simulate.main(["--nx", "32", "--ny", "32", "--t-end", "0.02",
                         "--dt", "0.005"])
    info(f"transient compile seconds {compile_seconds() - c0:.3f}")
    check(np.isfinite(res.voltages).all(), "transient voltages are finite")
    check(res.max_residual <= 1e-6,
          f"transient max Newton residual {res.max_residual:.3e} <= 1e-6")


def phase_four_chips(A, S, rng, B=64):
    """Scenario-sharded batch over 4 chips vs the same batch on one."""
    import jax

    from repro.circuit import rc_grid_circuit, transient_sweep
    from repro.core import GLU
    from repro.distributed import make_sweep_mesh

    check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices >= 4")
    mesh = make_sweep_mesh(4)
    vals = perturbed(A.data, rng, (B, A.nnz), 0.1)
    rhs = rng.standard_normal((B, A.n))
    g1 = GLU(A)
    t = time.perf_counter()
    X1 = g1.refactorize_solve(vals, rhs, refine=REFINE)
    info(f"unsharded B={B} first refactorize_solve seconds "
         f"{time.perf_counter() - t:.3f}")
    g4 = GLU(A, mesh=mesh)
    t = time.perf_counter()
    X4 = g4.refactorize_solve(vals, rhs, refine=REFINE)
    info(f"sharded B={B} first refactorize_solve seconds "
         f"{time.perf_counter() - t:.3f}")
    si = g4.solve_info
    check(si["n_devices"] == 4 and si["n_dispatches"] == 1,
          f"sharded over {si['n_devices']} devices, {si['n_dispatches']} "
          f"factorize dispatch, spec {si['batch_spec']}")
    placed = {s.device for s in g4._vals_batch.addressable_shards}
    check(placed == set(mesh.devices.flat),
          f"factor shards live on {len(placed)} distinct devices")
    worst = 0.0
    for i in range(B):
        worst = max(worst, backward_error(with_values(A, vals[i]), X4[i],
                                          rhs[i]))
    check(worst <= BERR_F64, f"sharded worst row backward error {worst:.3e}")
    same = np.array_equal(X1, X4)
    d = rel_diff(X4, X1)
    info(f"sharded vs unsharded solutions bit-identical: {same}")
    check(d <= 1e-12, f"sharded vs unsharded rel diff {d:.3e} <= 1e-12")
    g4.solve_batched(rhs, refine=0)
    check(g4.solve_info["solve_dispatches"] == 1,
          "sharded unrefined solve is one dispatch per shard")
    t = time.perf_counter()
    g4.refactorize_solve(vals, rhs, refine=REFINE)
    info(f"sharded B={B} warm refactorize_solve seconds "
         f"{time.perf_counter() - t:.4f}")

    ckt = rc_grid_circuit(32, 32, with_diodes=True, seed=SEED)
    scales = np.linspace(0.8, 1.2, 8)
    r1 = transient_sweep(ckt, 0.02, 0.005, scales=scales)
    r4 = transient_sweep(ckt, 0.02, 0.005, scales=scales, mesh=mesh)
    check(r4.n_devices == 4, f"transient_sweep sharded over {r4.n_devices}")
    check(np.isfinite(r4.voltages).all() and r4.max_residual <= 1e-6,
          f"sharded sweep finite, max residual {r4.max_residual:.3e}")
    d = rel_diff(r4.voltages, r1.voltages)
    info(f"sweep voltages bit-identical: "
         f"{np.array_equal(r4.voltages, r1.voltages)}")
    check(d <= 1e-12, f"sharded vs unsharded sweep rel diff {d:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded phase on four chips")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (backend {devs[0].platform!r}); "
              "nothing runs", file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache

    info(f"compilation cache {enable_compile_cache()}")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    info(f"device {devs[0].device_kind} x{len(devs)}, jax {jax.__version__}")

    from repro.sparse import make_suite_matrix

    rng = np.random.default_rng(SEED)
    A = make_suite_matrix(MATRIX, scale=1.0, seed=SEED)
    S = with_values(A, A.data)
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(A, S, rng)
    else:
        b = rng.standard_normal(A.n)
        glu, x64 = phase_default(A, S, b, rng)
        phase_batched(glu, A, S, rng)
        phase_pallas(A, S, b, x64)
        phase_transient()
    info(f"total compile seconds {compile_seconds():.3f} over "
         f"{_COMPILE['programs']} programs; wall "
         f"{time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


def _on_duration(event: str, duration: float, **_) -> None:
    # backend compiles, persistent-cache reads included
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE["seconds"] += duration
        _COMPILE["programs"] += 1


if __name__ == "__main__":
    sys.exit(main())
