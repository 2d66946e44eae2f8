"""Size a configuration's page pool from what the TPU compiler says its
served programs need, without a chip.

    JAX_PLATFORMS=cpu python3 bench/size_pool.py bench/configs/<name>.json

Compiles the engine's two cells (the ragged prefill lane and the decode
cell) at the config's serving settings for a described TPU v5e, with the
Pallas kernels lowered for the chip, and prints each program's
``memory_analysis()``, the weights' bytes, and the largest ``num_pages``
whose pool fits beside the weights and the larger program's temporaries
within ``--usable`` bytes.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="pool to compile with (default: the config's)")
    ap.add_argument("--usable", type=float, default=15.0e9,
                    help="device bytes the process may hold")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    from repro.kernels.decode_attention import ops
    from repro.models import get_model
    from bench.serve import arch_config

    ops.auto_interpret = lambda interpret=None: False   # lower for the chip
    conf = json.loads(Path(args.config).read_text())
    srv = conf["serving"]
    cfg = arch_config(conf)
    model = get_model(cfg)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    B, page = srv["max_batch"], srv["page_size"]
    T, chunk = srv["prefill_chunk_tokens"], srv["prefill_chunk"]
    nb = -(-conf["max_context"] // page)
    pages = args.num_pages or srv["num_pages"]

    def put(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    params = put(model.abstract_params())
    cache = put(model.abstract_paged_cache(B, nb, page, pages))
    cache = dict(cache, table=jax.ShapeDtypeStruct((B, nb), jnp.int32,
                                                   sharding=chip),
                 length=jax.ShapeDtypeStruct((B,), jnp.int32, sharding=chip))
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=chip)
    ints = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)  # noqa: E731

    prefill = jax.jit(model.prefill_many_paged,
                      static_argnames=("temperature",),
                      donate_argnums=(2, 3)).lower(
        params, ints(B, T), cache, key, ints(B), temperature=0.0).compile()
    decode = jax.jit(
        lambda p, tok, c, k, steps: model.decode_many_paged(
            p, tok, c, k, steps, None, None, num_steps=chunk,
            temperature=0.0), donate_argnums=(2, 3)).lower(
        params, ints(B, 1), cache, key, ints(B)).compile()

    w_bytes = sum(x.size * x.dtype.itemsize
                  for x in jax.tree.leaves(model.abstract_params()))
    page_bytes = (2 * cfg.n_layers * cfg.n_kv_heads * page * cfg.head_dim
                  * 2)
    out = {"weights_bytes": w_bytes, "page_bytes": page_bytes,
           "num_pages_compiled": pages}
    temps = []
    for name, c in (("prefill_lane", prefill), ("decode_cell", decode)):
        m = c.memory_analysis()
        out[name] = {k: getattr(m, k) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes")}
        temps.append(m.temp_size_in_bytes)
        assert "tpu_custom_call" in c.as_text(), name
    free = args.usable - w_bytes - max(temps)
    out["usable_bytes"] = args.usable
    out["num_pages_fit"] = int(free // page_bytes) + 1   # + the null page
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
