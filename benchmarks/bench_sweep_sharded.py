"""Sharded batch-sweep scaling: refactorize_solve over 1..8 devices.

Measures the mesh-sharded batched refactorize+solve engine (``GLU(...,
mesh=make_sweep_mesh(d))``) at a fixed batch size while the device count
grows.  Everything runs in ONE process: each device count is a sub-mesh of
``jax.devices()`` (the first ``d`` devices), so on a TPU host one process
holds every chip and no child ever competes for one.  Counts larger than
the devices present are skipped.

On the CPU, emulate a multi-device host by setting
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before JAX starts;
the emulated devices time-share the host's cores, so the honest expectation
there is ~1x.  Every row names the platform and device kind it ran on.
Every run still asserts the single-dispatch invariant: each shard executes
the whole fused schedule in ONE device dispatch (``n_dispatches == 1`` and
``solve_dispatches == 1``).

Row names use the ``sweep_sharded_`` prefix, which is intentionally NOT in
the perf-diff gate (``benchmarks.diff`` gates ``factorize_``/``ac_``).
"""
from __future__ import annotations

import os
import sys
import time

from .common import row

DEVICE_COUNTS = [1, 2, 4, 8]
BATCH = 64
REPEATS = 3
CIRCUIT_N = 600


def _time_sweep(n_devices: int, batch: int, repeats: int, size: int) -> dict:
    """Build one sweep problem on the first ``n_devices`` devices and time
    its refactorize_solve (the result comes back to the host, so each
    timing ends after the device finished)."""
    import numpy as np

    from repro.core import GLU
    from repro.distributed import make_sweep_mesh
    from repro.sparse import circuit_jacobian

    mesh = make_sweep_mesh(n_devices) if n_devices > 1 else None
    A = circuit_jacobian(size, avg_degree=4.5, seed=5)
    glu = GLU(A, mesh=mesh)

    rng = np.random.default_rng(0)
    vals = np.asarray(A.data)[None] * (
        1.0 + 0.1 * rng.uniform(-1, 1, size=(batch, A.nnz)))
    rhs = rng.normal(size=(batch, A.n))

    glu.refactorize_solve(vals, rhs)            # compile + warm up
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        glu.refactorize_solve(vals, rhs)
        ts.append(time.perf_counter() - t0)
    info = glu.solve_info
    return {"elapsed_s": min(ts), "n_devices": info["n_devices"],
            "batch_spec": info["batch_spec"],
            "n_dispatches": info["n_dispatches"],
            "solve_dispatches": info["solve_dispatches"]}


def main(smoke: bool = False):
    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    counts = [d for d in ([1, 2] if smoke else DEVICE_COUNTS)
              if d <= len(devices)]
    batch = 8 if smoke else BATCH
    repeats = 1 if smoke else REPEATS
    size = 200 if smoke else CIRCUIT_N
    cores = os.cpu_count() or 1

    print(f"# sweep_sharded: B={batch} refactorize_solve on {platform} "
          f"({kind}), {len(devices)} device(s), {cores} host cores")
    print("# devices,us_per_matrix,speedup_vs_d1,n_dispatches")

    per_matrix_d1 = None
    results = []
    for d in counts:
        r = _time_sweep(d, batch, repeats, size)
        assert r["n_devices"] == d, r
        assert r["n_dispatches"] == 1, r
        assert r["solve_dispatches"] == 1, r
        per_matrix = r["elapsed_s"] / batch
        if per_matrix_d1 is None:
            per_matrix_d1 = per_matrix
        speedup = per_matrix_d1 / per_matrix
        print(f"{d},{per_matrix * 1e6:.1f},{speedup:.2f},1", flush=True)
        row(f"sweep_sharded_d{d}", per_matrix * 1e6,
            f"batch={batch} speedup_vs_d1={speedup:.2f}x "
            f"spec={r['batch_spec']} dispatches=1 platform={platform} "
            f"device_kind={kind} cores={cores}")
        results.append({"devices": d, "per_matrix_s": per_matrix,
                        "speedup_vs_d1": speedup})
    best = max(results, key=lambda r: r["speedup_vs_d1"])
    print(f"# best scaling: {best['speedup_vs_d1']:.2f}x at "
          f"{best['devices']} devices (single-dispatch held on every run)")
    return results


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv)
