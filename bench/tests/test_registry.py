"""Everything ``BENCHMARK.json`` names is found by name, a file-only
addition is picked up, and the frozen generators still give the program's
suite matrices."""
import hashlib
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


# SHA-256 of the arrays' bytes as the generators in bench/gen.py made them
# before they moved to bench/generators/ (configuration args, pattern seed 0)
DIGESTS = {
    "randjac1879": {
        "indptr": "1e0781ae483b55a56bd82eb2ea2e40da93f6a119e642799c12b920b222f9e84f",
        "indices": "cc07195eaa49177708dfe53694ed8bdae8b4b4d7251c6fad22e44b1d0dae24d8",
        "data": "9c6790619f50020cd398f50d41f9715d975f3be5624d67d9d4b023885c3597ae",
        "value_pool": "311225a1a4386a19fcad57bdd86136b9b67382d0a458379d81ae0d41dd01583b"},
    "rcladder17758": {
        "indptr": "cc18004a01290795b1bf98cf048989158179e3434e14a5f962d9797aad3ee595",
        "indices": "8632c217d4fa18242c6a7c41641da166a971b135e0c5c71387b4df01db8f0bf2",
        "data": "83ede5f42ef8611a1eead6597e1c185ed4a8aefcb5467688420b6560ae8b5ab7",
        "value_pool": "6117fba42562cdf22e44438023cd55c9d4cda50751bfe7b0209c84e938fe0083"},
}


@pytest.mark.parametrize("config", sorted(DIGESTS))
def test_moved_generator_gives_the_same_bytes(config):
    n, (indptr, indices, data), net = gen.make_matrix(
        run.load_config(SPEC, config))
    pool = gen.value_pool(net, np.random.default_rng(3), 2, 0.1)
    got = {k: hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()
           for k, a in (("indptr", indptr), ("indices", indices),
                        ("data", data), ("value_pool", pool))}
    assert got == DIGESTS[config]


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


# a matrix class the benchmark does not have: a ring of n nodes, each
# coupled to its two neighbours, with a leak on every node
RING = """import numpy as np

from gen import Netlist, csc_from_coo


def make(n, seed=0):
    g = np.random.default_rng(seed).uniform(0.5, 2.0, size=n)
    i = np.arange(n)
    j = (i + 1) % n
    rows, cols = np.concatenate([i, j]), np.concatenate([j, i])
    vals = np.concatenate([-g, -g])
    net = Netlist(n, rows, cols, vals, np.concatenate([i, i]), n, 0.1)
    diag = 0.1 + np.bincount(rows, weights=np.abs(vals), minlength=n)
    return n, csc_from_coo(n, np.concatenate([rows, i]),
                           np.concatenate([cols, i]),
                           np.concatenate([vals, diag])), net
"""


@pytest.mark.parametrize("added", ["config_mix_metric", "generator"])
def test_a_file_only_addition_is_picked_up(added, tmp_path):
    """Added files and entries, in a copy of the benchmark, make a new cell
    that ``run_cell`` runs with no edit to any file that was there: a
    configuration of an existing generator with a new mix and a new
    per-layer metric, or a new generator with a configuration of it."""
    bench = tmp_path / "bench"
    shutil.copytree(run.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    if added == "generator":
        (bench / "generators" / "ring.py").write_text(RING)
        config = {"name": "ring_small", "generator": "ring",
                  "args": {"n": 40, "seed": 0}}
        traffic = "newton"
    else:
        config = {"name": "ladder_small", "generator": "rc_ladder",
                  "args": {"n": 50, "seed": 0}}
        traffic = "newton_k2"
        (bench / "mixes" / "newton_k2.json").write_text(json.dumps(
            {**run.load_mix("newton"), "refine": 2}))
    name = config["name"]
    cell = f"{name}.{traffic}"
    (bench / "configs" / f"{name}.json").write_text(json.dumps({
        **config, "value_dtype": "float64",
        "limits": {"berr": 1e-12, "fwd_err": 1e-9}}))
    (bench / "metrics" / "steps_traced.newton.py").write_text(
        "def read(ctx):\n    return ctx.get('steps')\n")
    spec["configs"].append({"name": name, "source": "x",
                            "file": f"bench/configs/{name}.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": cell, "config": name,
                              "traffic": traffic, "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "steps_traced.newton", "unit": "steps",
                              "better": "higher", "source": "program_counter",
                              "layer": "device", "moves": "newton_step_ms",
                              "workloads": [cell]})
    for m in spec["end_to_end"]:
        if m["name"].startswith("newton_step"):
            m["workloads"].append(cell)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    spec2 = run.load_spec(tmp_path)
    w = run.cell(spec2, cell)
    cfg = run.load_config(spec2, w["config"], tmp_path)
    assert gen.make_matrix(cfg, bench)[0] == config["args"]["n"]
    assert run.load_mix(w["traffic"], bench)["refine"] == (
        2 if traffic == "newton_k2" else 3)
    names = [m["name"] for m in run.metrics_of(spec2["per_layer"], w["name"])]
    assert "steps_traced.newton" in names
    assert run.load_reader("steps_traced.newton", bench)({"steps": 3}) == 3
    r = run.run_cell(cell, 5, 0.2, False, spec=spec2, require_tpu=False,
                     root=tmp_path)
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
