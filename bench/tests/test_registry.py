"""Everything ``BENCHMARK.json`` names is found by name, a file-only
addition is picked up, and the frozen generators still give the program's
suite matrices."""
import json
import shutil

import numpy as np
import pytest

import gen
import run

SPEC = run.load_spec()


@pytest.mark.parametrize("config,suite", [("randjac1879", "rajat12_like"),
                                          ("rcladder17758", "memplus_like")])
def test_frozen_generator_matches_the_program_suite(config, suite):
    from repro.sparse import make_suite_matrix

    A = make_suite_matrix(suite, scale=1.0, seed=0)
    n, (indptr, indices, data), _ = gen.make_matrix(
        run.load_config(SPEC, config))
    assert n == A.n
    assert np.array_equal(indptr, A.indptr)
    assert np.array_equal(indices, A.indices)
    assert np.array_equal(data, np.asarray(A.data))


def test_every_named_piece_loads():
    for c in SPEC["configs"]:
        cfg = run.load_config(SPEC, c["name"])
        assert cfg["name"] == c["name"]
        assert set(cfg["limits"]) == {"berr", "fwd_err"}
        assert gen.make_matrix(cfg)[0] == cfg["args"]["n"]
    for w in SPEC["workloads"]:
        mix = run.load_mix(w["traffic"])
        assert mix["kind"] in ("newton", "sweep")
        assert run.metrics_of(SPEC["end_to_end"], w["name"])
        assert run.metrics_of(SPEC["per_layer"], w["name"])
    for m in SPEC["per_layer"]:
        assert run.load_reader(m["name"])({}) is None


def test_a_file_only_addition_is_picked_up(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(run.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    (bench / "configs" / "ladder_small.json").write_text(json.dumps({
        "name": "ladder_small", "generator": "rc_ladder",
        "args": {"n": 50, "seed": 0}, "value_dtype": "float64",
        "limits": {"berr": 1e-12, "fwd_err": 1e-9}}))
    (bench / "mixes" / "newton_k2.json").write_text(json.dumps(
        {**run.load_mix("newton"), "refine": 2}))
    (bench / "metrics" / "steps_traced.newton.py").write_text(
        "def read(ctx):\n    return ctx.get('steps')\n")
    spec["configs"].append({"name": "ladder_small", "source": "x",
                            "file": "bench/configs/ladder_small.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "ladder_small.newton_k2",
                              "config": "ladder_small",
                              "traffic": "newton_k2", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "steps_traced.newton", "unit": "steps",
                              "better": "higher", "source": "program_counter",
                              "layer": "device", "moves": "newton_step_ms",
                              "workloads": ["ladder_small.newton_k2"]})
    for m in spec["end_to_end"]:
        if m["name"].startswith("newton_step"):
            m["workloads"].append("ladder_small.newton_k2")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    spec2 = run.load_spec(tmp_path)
    w = run.cell(spec2, "ladder_small.newton_k2")
    cfg = run.load_config(spec2, w["config"], tmp_path)
    assert gen.make_matrix(cfg)[0] == 50
    assert run.load_mix(w["traffic"], bench)["refine"] == 2
    names = [m["name"] for m in run.metrics_of(spec2["per_layer"], w["name"])]
    assert "steps_traced.newton" in names
    assert run.load_reader("steps_traced.newton", bench)({"steps": 3}) == 3
    r = run.run_cell("ladder_small.newton_k2", 5, 0.2, False, spec=spec2,
                     require_tpu=False, root=tmp_path)
    assert r["correct"] and set(r["metrics"]) == {
        "setup_s", "newton_step_ms", "newton_step_p95_ms"}


@pytest.mark.parametrize("config", ["randjac1879", "rcladder17758"])
def test_restamped_values_keep_each_branch_stamp(config):
    """Scaling one branch moves its two off-diagonal entries and the two
    diagonals it stamps by the same conductance, and nothing else; at unit
    factors the matrix comes back, and every row keeps its leak."""
    import scipy.sparse as sp

    n, (indptr, indices, data), net = gen.make_matrix(
        run.load_config(SPEC, config))
    base = net.restamp(np.ones(net.n_branches))
    np.testing.assert_allclose(base, data, rtol=1e-14, atol=1e-14)
    b = net.n_branches // 2
    f = np.ones(net.n_branches)
    f[b] = 1.1
    moved = np.flatnonzero(net.restamp(f) != base)
    assert 2 <= len(moved) <= 4
    v = gen.value_pool(net, np.random.default_rng(3), 2, 0.1)
    S = sp.csc_matrix((v[1], indices, indptr), shape=(n, n))
    off = S - sp.diags(S.diagonal())
    leak = S.diagonal() - np.asarray(abs(off).sum(axis=1)).ravel()
    np.testing.assert_allclose(leak, leak[0], rtol=1e-9)
    assert leak[0] > 0
    assert np.all(np.asarray(S.sum(axis=1)).ravel() > 0)
