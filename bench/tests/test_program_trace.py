"""The reduction by the program's own spans and program names: on
hand-made intervals, and on two traces recorded on a TPU v5e by
``record_trace.py`` (two Newton steps of a 64-node ladder each), one from
before the program had spans and fixed program names and one after."""
from pathlib import Path

import numpy as np
import pytest

import jax.numpy as jnp
import program_trace as pt
import trace as tm
from gen import make_matrix
from repro.core import GLU
from repro.sparse.csc import CSC

DATA = Path(__file__).parent / "data"
OLD = DATA / "newton_tpu.xplane.pb"
GLU_TRACE = DATA / "newton_tpu_glu.xplane.pb"
STEPS = 2
LADDER = {"generator": "rc_ladder", "args": {"n": 64, "seed": 0}}
# one Newton step, factorize + solve(refine=3), of the recorded ladder
STEP_PROGRAMS = {"glu_factorize": 1, "abs": 1, "glu_trisolve": 4,
                 "glu_residual": 4, "convert_element_type": 1,
                 "glu_correct": 3, "greater": 3, "add": 3}
# what the same programs were called before they had fixed names
OLD_NAMES = {"glu_factorize": "run", "glu_trisolve": "_solve_schedule_body",
             "glu_residual": "_residual_berr",
             "glu_correct": "masked_correction"}


def test_program_name():
    assert pt.program_name("jit_glu_trisolve(10050744479494182163)") == \
        "glu_trisolve"
    assert pt.program_name("jit__residual_berr(51)") == "_residual_berr"
    assert pt.program_name("jit_add") == "add"


def test_clock_offset_starts_no_module_before_its_launch():
    modules = [("a", 5, 8), ("b", 22, 25), ("c", 27, 29)]
    assert pt.clock_offset([30, 10, 20], modules) == 5
    assert pt.clock_offset([1, 2, 3], modules) == 0.0
    with pytest.raises(ValueError, match="2 host launches"):
        pt.clock_offset([1, 2], modules)


def test_device_offsets_fall_back_to_each_cores_launches():
    """On a mesh the first device runs every program and the others only
    the sharded ones: those are aligned to their own core's launches."""
    modules = {"/device:TPU:0": [("a", 5, 8), ("b", 22, 25), ("c", 27, 29)],
               "/device:TPU:1": [("a", 6, 8), ("c", 26, 29)]}
    cores = {0: [12, 22, 31], 1: [10, 30]}
    assert pt.device_offsets([10, 20, 30], cores, modules) == {
        "/device:TPU:0": 5, "/device:TPU:1": 4}
    with pytest.raises(ValueError, match="0 host launches"):
        pt.device_offsets([10, 20, 30], {1: [10, 30]},
                          {"/device:TPU:2": modules["/device:TPU:0"][:2]})


def test_reduce_by_hand():
    spans = [("bench.window", 0, 100), ("bench.step", 0, 60),
             ("glu.solve", 10, 50), ("glu.refine", 20, 40),
             ("glu.sync", 30, 35), ("bench.step", 60, 100)]
    dev = "/device:TPU:0"
    ops = {dev: [("fusion.1", 0, 10), ("while.2", 22, 28),
                 ("fusion.3", 40, 45), ("fusion.4", 70, 80)]}
    modules = {dev: [("glu_factorize", 0, 10), ("glu_trisolve", 21, 29),
                     ("add", 40, 46), ("glu_factorize", 70, 80),
                     ("glu_factor_stats", 101, 102)]}
    r = pt.reduce(spans, ops, modules, {dev: 0.0})
    ns = 1e-9
    assert r["busy_s"] == pytest.approx(31 * ns)
    assert r["program_device_s"] == {"glu_factorize": pytest.approx(20 * ns),
                                     "glu_trisolve": pytest.approx(6 * ns),
                                     "other": pytest.approx(5 * ns)}
    assert r["program_count"] == 4
    assert r["program_count_by_name"] == {"glu_factorize": 2,
                                          "glu_trisolve": 1, "add": 1}
    # idle: step [10,60) less busy, split by the innermost span
    assert r["span_idle_s"] == {
        "glu.solve": pytest.approx((10 + 5) * ns),     # 10-20, 45-50
        "glu.refine": pytest.approx((2 + 2 + 5) * ns),  # 20-22, 28-30, 35-40
        "glu.sync": pytest.approx(5 * ns),
        "bench.step": pytest.approx((10 + 10 + 20) * ns),
    }
    assert sum(r["span_idle_s"].values()) == pytest.approx(
        (100 - 10 - 6 - 5 - 10) * ns)
    assert r["idle_gaps"] == [["bench.step", pytest.approx(25 * ns)],
                              ["bench.step", pytest.approx(20 * ns)],
                              ["glu.solve", pytest.approx(12 * ns)],
                              ["glu.sync", pytest.approx(12 * ns)]]


def _reduce(path):
    spans, ops = tm.read(str(path), [0])
    pspans, modules, offsets = pt.read(str(path), [0])
    return (tm.reduce(spans, ops), pt.reduce(pspans, ops, modules, offsets),
            pspans)


def _busy_by_name_adds_up(base, r):
    """Every op of the window runs inside some module (to the few ns by
    which an op's stamp can pass its module's), and idle is the window less
    busy."""
    total = sum(r["program_device_s"].values())
    assert total == pytest.approx(r["busy_s"], rel=1e-5)
    assert sum(r["span_idle_s"].values()) == pytest.approx(
        base["window_s"] - r["busy_s"], rel=1e-6)


def test_old_trace_has_accidental_names_and_no_program_spans():
    base, r, spans = _reduce(OLD)
    assert not [s for s in spans if s[0].startswith(pt.GLU_PREFIX)]
    assert set(r["program_device_s"]) == {pt.OTHER}
    assert r["program_count_by_name"] == {
        OLD_NAMES.get(n, n): STEPS * c for n, c in STEP_PROGRAMS.items()}
    _busy_by_name_adds_up(base, r)


@pytest.fixture(scope="module")
def glu_trace():
    return _reduce(GLU_TRACE)


def test_glu_trace_programs_per_step_match_the_counters(glu_trace):
    """Per step the chip ran what ``solve_info`` counts for the same step:
    ``n_dispatches`` + ``solve_dispatches``, name by name.  The chip's
    refinement ran all three sweeps (three corrections a step); the CPU
    meets the f64 tolerance sooner, so it is held to a tolerance no sweep
    meets."""
    _, r, _ = glu_trace
    per_step = {n: c / STEPS for n, c in r["program_count_by_name"].items()}
    assert per_step == STEP_PROGRAMS
    n, (indptr, indices, data), _ = make_matrix(LADDER)
    A = CSC(n, indptr, indices, data)
    glu = GLU(A, dtype=jnp.float64, plan_cache=None, refine_tol=0.0)
    glu.factorize(data)
    glu.solve(np.ones(n), refine=3)
    info = glu.solve_info
    assert info["refine_iters"] == 3
    assert (info["n_dispatches"] + info["solve_dispatches"]
            == r["program_count"] / STEPS == sum(STEP_PROGRAMS.values()))


def test_glu_trace_device_time_by_program(glu_trace):
    base, r, _ = glu_trace
    dev = r["program_device_s"]
    # glu_factor_stats: the benchmark's solve_info read between the steps
    assert set(dev) == {"glu_factorize", "glu_trisolve", "glu_residual",
                        "glu_correct", "glu_factor_stats", pt.OTHER}
    # bench.factorize holds the same work, give or take the ops that the
    # device/host clock skew moves across its ends (trace.py does not align)
    assert dev["glu_factorize"] == pytest.approx(
        base["span_device_s"]["bench.factorize"], rel=0.01)
    _busy_by_name_adds_up(base, r)


def test_glu_trace_idle_inside_a_step_is_named_by_program_spans():
    """Every device idle gap of over 50 us inside a step falls in one of
    the program's spans, but for the benchmark's own wait for the factors
    in ``bench.factorize``."""
    _, ops = tm.read(str(GLU_TRACE), [0])
    pspans, modules, offsets = pt.read(str(GLU_TRACE), [0])
    r = pt.reduce(pspans, ops, modules, offsets, top=10_000)
    steps = [s for s in pspans if s[0] == "bench.step"]
    assert len(steps) == STEPS
    inside = [name for name, secs in r["idle_gaps"]
              if secs > 50e-6 and name != tm.WINDOW]
    assert inside
    assert {n for n in inside if not n.startswith(pt.GLU_PREFIX)} == \
        {"bench.factorize"}
    assert {"glu.h2d", "glu.refine", "glu.sync"} <= set(inside)


def _launch_order(path):
    spans, modules, offsets = pt.read(str(path), [0])
    dev = "/device:TPU:0"
    calls = [s for s in spans if s[0] == "bench.step"]
    return [name for name, s, _ in modules[dev]
            if any(c[1] <= s + offsets[dev] < c[2] for c in calls)]


def test_glu_trace_runs_the_old_programs_in_the_old_order():
    """Naming changed no program: the steps launch the same modules in the
    same order as before, under their new names."""
    rename = {old: new for new, old in OLD_NAMES.items()}
    old = [rename.get(n, n) for n in _launch_order(OLD)]
    assert old == _launch_order(GLU_TRACE)


def test_reduce_shifts_each_device_by_its_offset():
    spans = [("bench.window", 0, 100), ("glu.solve", 40, 60)]
    dev = "/device:TPU:0"
    ops = {dev: [("fusion.1", 30, 40)]}
    modules = {dev: [("glu_trisolve", 30, 40)]}
    r = pt.reduce(spans, ops, modules, {dev: 15.0})
    # shifted to 45-55: inside glu.solve, which is idle 40-45 and 55-60
    assert r["span_idle_s"]["glu.solve"] == pytest.approx(10e-9)
    assert r["program_device_s"] == {"glu_trisolve": pytest.approx(10e-9)}


def test_programs_reduction_bounds_the_ops_busy_time_from_above():
    """The ``"programs"`` reduction of the recorded trace reads busy time
    from the modules, aligned to the host: at or above the ``"ops"``
    reduction of the same trace, which unions the ops.  A module also
    covers its program's start and end, where no op runs: on this trace
    about 1.1 us at each end of 40 short programs, 1.0% of the 4.51 ms the
    ops are busy; 2% allows twice that.  The window, the spans and the
    names by program are the same trace's."""
    import run

    ops, _ = run.reduce_trace(str(GLU_TRACE), [0], "ops")
    programs, by_program = run.reduce_trace(str(GLU_TRACE), [0], "programs")
    assert programs["window_s"] == ops["window_s"]
    assert programs["span_count"] == ops["span_count"]
    assert ops["busy_s"] <= programs["busy_s"] <= 1.02 * ops["busy_s"]
    assert programs["busy_s"] == pytest.approx(by_program["busy_s"])
    names = [n for n, _ in programs["device_ops"]]
    assert names[:3] == ["glu_trisolve", "glu_factorize", "glu_residual"]
    assert "glu_correct" in names
    assert by_program["program_count"] / STEPS == sum(STEP_PROGRAMS.values())
