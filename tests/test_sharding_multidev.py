"""Multi-device sharding tests — run in a SUBPROCESS with 8 host devices so
the main test process keeps the single real CPU device (per dry-run policy,
XLA_FLAGS must never be set globally)."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get
from repro.dist.sharding import MeshRules, use_mesh
from repro.models import get_model
from repro.core.hlo_counters import census_from_compiled

out = {}

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = MeshRules(batch_axes=("data",), fsdp_axes=("data",),
                  model_axis="model")

# 1) sharded MoE forward == unsharded reference (the shard_map island)
cfg = get("llama4-scout-17b-a16e").reduced()   # E=4, ep_shards=4
model = get_model(cfg)
params = model.init(jax.random.key(0))
B, S = 4, 32
rng = np.random.RandomState(0)
batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S))),
         "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S)))}
loss_ref = float(jax.jit(model.loss)(params, batch))

from repro.train.elastic import reshard
with use_mesh(mesh, rules):
    p_sh = reshard(params, model.param_pspecs(rules), mesh)
    batch_sh = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P("data"))), batch)
    loss_sharded = float(jax.jit(model.loss)(p_sh, batch_sh))
out["moe_loss_ref"] = loss_ref
out["moe_loss_sharded"] = loss_sharded

# 2) collective census on a real SPMD program
def f(x, w):
    return jnp.tanh(x @ w).sum()

xs = jax.ShapeDtypeStruct((64, 128), jnp.float32)
ws = jax.ShapeDtypeStruct((128, 64), jnp.float32)
with mesh:
    compiled = jax.jit(
        f, in_shardings=(NamedSharding(mesh, P("data", "model")),
                         NamedSharding(mesh, P("model", None)))
    ).lower(xs, ws).compile()
census = census_from_compiled(compiled)
out["n_partitions_collectives"] = {
    k: v.count for k, v in census.collectives.items()}
out["collective_wire_bytes"] = census.collective_wire_bytes
print("RESULT" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def subprocess_result():
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=os.path.dirname(os.path.dirname(__file__)))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


def test_moe_sharded_matches_reference(subprocess_result):
    r = subprocess_result
    assert abs(r["moe_loss_sharded"] - r["moe_loss_ref"]) \
        / abs(r["moe_loss_ref"]) < 2e-2, r


def test_collective_census_nonzero(subprocess_result):
    r = subprocess_result
    assert r["collective_wire_bytes"] > 0
    assert any(k in r["n_partitions_collectives"]
               for k in ("all-reduce", "reduce-scatter", "all-gather"))
