"""Sharding rules resolution + an 8-device host-mesh integration test
(run in a subprocess so the main test process keeps 1 device)."""
import subprocess
import sys
from pathlib import Path

from repro.configs import get_config
from repro.distributed.sharding import make_rules


def test_make_rules_respects_attn_tp():
    whisper = get_config("whisper-base")
    rules = make_rules(whisper)
    assert rules["heads"] is None and rules["kv_heads"] is None
    qwen = get_config("qwen2.5-3b")
    rules = make_rules(qwen)
    assert rules["heads"] == "model"


def test_rules_override():
    rules = make_rules(get_config("qwen2.5-3b"), kv_seq="model")
    assert rules["kv_seq"] == "model"


_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.distributed.sharding import (axis_env, make_rules, tree_shardings,
                                        logical_constraint, sharding_for_spec)
from repro.configs import get_config
from repro.models.model import param_specs

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto, AxisType.Auto))
cfg = get_config("qwen2.5-3b").reduced()
rules = make_rules(cfg)

# 1. params shard over the mesh without error, divisibility guard works
specs = param_specs(cfg)
shs = tree_shardings(specs, mesh, rules, fsdp=True)
emb_sh = shs["embed"]
assert emb_sh.spec[0] == "model", emb_sh.spec       # vocab 512 % 4 == 0

# 2. logical_constraint inside jit produces the annotated sharding
with axis_env(mesh, rules):
    @jax.jit
    def f(x):
        return logical_constraint(x * 2, "batch", None)
    x = jnp.ones((8, 16))
    y = f(x)
    assert y.sharding.spec[0] == ("data",) or y.sharding.spec[0] == "data", y.sharding

# 3. duplicate-axis guard: experts and expert_ffn both -> model
sh = sharding_for_spec((4, 8, 16), ("experts", None, "expert_ffn"), mesh, rules)
flat = [a for s in sh.spec if s for a in (s if isinstance(s, tuple) else (s,))]
assert len(flat) == len(set(flat)), sh.spec

# 4. a sharded einsum runs end-to-end on 8 devices
with axis_env(mesh, rules):
    @jax.jit
    def g(w, x):
        x = logical_constraint(x, "batch", None)
        return x @ w
    w = jax.device_put(np.ones((16, 32), np.float32),
                       NamedSharding(mesh, P(None, "model")))
    out = g(w, jnp.ones((8, 16)))
    assert out.shape == (8, 32)
print("SUBPROCESS_OK")
"""


def test_eight_device_mesh_integration():
    r = subprocess.run([sys.executable, "-c", _SUBPROC], capture_output=True,
                       text=True, cwd=Path(__file__).resolve().parents[1],
                       timeout=300)
    assert "SUBPROCESS_OK" in r.stdout, r.stdout + "\n" + r.stderr[-3000:]
