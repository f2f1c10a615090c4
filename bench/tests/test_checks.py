"""``correct`` holds for a sound run and fails for the control and for each
fault a cell can have.  Each run drives the whole of ``run_cell`` except
the look for a chip, at a size the CPU holds, with the timed path broken
underneath where the test says so."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

import cpu_trace
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
# readers of what the program itself records: GLU.setup_seconds,
# solve_info and its spans and program names in the trace
READS_PROGRAM = ["executor_build_s", "host_io_idle_ms.newton",
                 "refine_idle_ms.newton", "programs_per_step.newton",
                 "factorize_dev_ms.sweep", "solve_dev_ms.sweep",
                 "trisolve_indexed_entries.newton",
                 "trisolve_dense_tail.newton"]


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
    cpu_trace.install(monkeypatch)
    spec = run.load_spec()
    r = run.run_cell(workload, 2**31 + 23, 5.0, True, spec=spec,
                     config=small_config(workload), require_tpu=False,
                     t0=time.perf_counter())
    want = {m["name"] for m in run.metrics_of(spec["per_layer"], workload)}
    assert set(r["metrics"]) == want
    assert r["correct"]
    dev = r["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert list(r["info"]["busy_s_by_device"]) == ["/device:TPU:0"]
    for name, m in r["metrics"].items():
        assert m["value"] >= 0, name
    assert r["breakdown"]["device_ops"][0][0] == "op"


def test_a_newton_mix_on_several_chips_is_refused_before_it_runs():
    spec = json.loads(json.dumps(run.load_spec()))
    spec["workloads"].append({"name": "rcladder17758.newton.4chip",
                              "config": "rcladder17758", "traffic": "newton",
                              "chips": 4, "why": "x"})
    with pytest.raises(ValueError, match="does not shard"):
        run.run_cell("rcladder17758.newton.4chip", 1, 1.0, False, spec=spec,
                     require_tpu=False)


def test_every_new_reader_reads_nothing_without_the_programs_readings():
    """On a traced run's ``ctx`` from a tree whose program writes no spans,
    counters or setup record, the readers of them return nothing."""
    ctx = {"plan_build_s": 1.0, "compile_s": 1.0, "kind": "newton",
           "steps": 1, "refine_iters": [3],
           "trace": {"window_s": 1.0, "busy_s": 0.5,
                     "span_device_s": {"bench.factorize": 0.1,
                                       "bench.solve": 0.4},
                     "span_count": {"bench.factorize": 1, "bench.solve": 1}}}
    for name in READS_PROGRAM:
        for kind in ("newton", "sweep"):
            assert run.load_reader(name)({**ctx, "kind": kind}) is None, name


def test_a_traced_sweep_call_reads_no_solve_info_inside_the_window():
    """Reading ``solve_info`` launches a program and waits for it.  A
    traced Newton step reads it after the step, as it always has; a sweep
    call leaves it to ``run_cell``, once the window has closed, so the
    sweep cells' traced windows hold their calls alone."""
    from traffic import Traffic

    class Glu:
        def refactorize_solve(self, vals, rhs, refine):
            return rhs

        @property
        def solve_info(self):
            raise AssertionError("solve_info read inside the window")

    cfg = small_config("randjac1879.sweep16")
    _, _, net = run.gen.make_matrix(cfg)
    traffic = Traffic(run.load_mix("sweep16"), net, 2**31 + 31)
    traffic.step(Glu(), 0, traced=True)
    assert traffic.solve_info == [] and len(traffic.outputs) == 1
