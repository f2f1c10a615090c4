"""``correct`` holds for a sound run and fails for the control and for each
fault a cell can have.  Each run drives the whole of ``run_cell`` except
the look for a chip, at a size the CPU holds, with the timed path broken
underneath where the test says so."""
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

import run
from faults import FAULTS

BENCH = Path(__file__).resolve().parents[1]

SMALL = {
    "randjac1879": {"generator": "circuit_jacobian",
                    "args": {"n": 160, "avg_degree": 6.9, "seed": 0}},
    "rcladder17758": {"generator": "rc_ladder", "args": {"n": 400, "seed": 0}},
}

# the faults each cell can have
CASES = [
    ("randjac1879.newton", "state_unchanged"),
    ("randjac1879.newton", "answer_altered"),
    ("rcladder17758.newton", "state_unchanged"),
    ("rcladder17758.newton", "answer_altered"),
    ("randjac1879.sweep16", "state_unchanged"),
    ("randjac1879.sweep16", "answer_altered"),
    ("randjac1879.sweep16", "half_batch"),
]
WORKLOADS = ["randjac1879.newton", "rcladder17758.newton",
            "randjac1879.sweep16"]


def small_config(workload):
    spec = run.load_spec()
    cfg = run.load_config(spec, run.cell(spec, workload)["config"])
    return {**cfg, **SMALL[cfg["name"]]}


def run_small(workload, seed, value_dtype=None):
    return run.run_cell(workload, seed, 0.5, False,
                        config=small_config(workload),
                        value_dtype=value_dtype, require_tpu=False,
                        t0=time.perf_counter())


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(workload):
    r = run_small(workload, 2**31 + 17)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("workload", WORKLOADS)
def test_float32_control_is_not_correct(workload):
    r = run_small(workload, 2**31 + 19, value_dtype="float32")
    assert not r["correct"]
    assert r["checks"]["berr"]["value"] > r["checks"]["berr"]["limit"]


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_is_not_correct(workload, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    r = run_small(workload, 2**32 + 3)
    assert not r["correct"], (fault, r["checks"])
    assert r["failed"] > 0


def test_no_tpu_exits_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "randjac1879.newton", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=BENCH.parent)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("workload", ["randjac1879.newton",
                                      "randjac1879.sweep16"])
def test_traced_run_reports_every_per_layer_metric(workload, monkeypatch):
    """The traced branch end to end on the CPU, whose trace has no TPU
    plane: the device is made busy exactly inside the call spans."""
    import trace as tm

    real = tm.read

    def read(path, device_ids):
        assert list(device_ids) == [0]
        with pytest.raises(ValueError, match="no device plane"):
            real(path, device_ids)
        spans, _ = real(path, [])
        calls = [s for s in spans
                 if s[0] in ("bench.factorize", "bench.solve", "bench.sweep")]
        return spans, {"/device:TPU:0": [("op", s, e) for _, s, e in calls]}

    monkeypatch.setattr(tm, "read", read)
    spec = run.load_spec()
    r = run.run_cell(workload, 2**31 + 23, 5.0, True, spec=spec,
                     config=small_config(workload), require_tpu=False,
                     t0=time.perf_counter())
    want = {m["name"] for m in run.metrics_of(spec["per_layer"], workload)}
    assert set(r["metrics"]) == want
    assert r["correct"]
    dev = r["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    for name, m in r["metrics"].items():
        assert m["value"] >= 0, name
    assert r["breakdown"]["device_ops"][0][0] == "op"
