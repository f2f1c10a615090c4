"""The four-chip cell on four virtual CPU devices, each case a process of
its own (``four_devices.py``): a sound run is correct and sharded over the
four, the float32 control and every fault the cell can have are not, and
the traced run reports every per-layer metric the cell lists."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

import run

SCRIPT = Path(__file__).parent / "four_devices.py"
CELL = "rcladder17758.sweep100.4chip"


def four_devices(case):
    p = subprocess.run([sys.executable, str(SCRIPT), case],
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct_and_sharded():
    r = four_devices("sound")
    assert r["correct"], r["checks"]
    assert r["info"]["shards"] == 4
    assert r["attempted"] % 100 == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"setup_s", "sweep_mps"}


@pytest.mark.parametrize("case", ["float32", "state_unchanged",
                                  "answer_altered", "half_batch",
                                  "no_exchange"])
def test_control_and_faults_are_not_correct(case):
    r = four_devices(case)
    assert not r["correct"], (case, r["checks"])
    assert r["failed"] > 0


def test_traced_run_reports_every_per_layer_metric():
    r = four_devices("traced")
    spec = run.load_spec()
    want = {m["name"] for m in run.metrics_of(spec["per_layer"], CELL)}
    assert set(r["metrics"]) == want
    assert r["correct"]
    assert r["device"]["count"] == 4
    assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]
    assert {n for n, _ in r["breakdown"]["device_ops"]} == {
        "glu_factorize", "glu_trisolve"}
