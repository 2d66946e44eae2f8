"""Both paged Pallas kernels compile for a described TPU v5e at
phi4-mini-3.8b's published attention widths (24 heads / 8 KV heads, head
dim 128, 16-token pages), bf16 and int8 pools, one and four pages per grid
step.  Nothing runs: the TPU compiler is asked for the executable, and the
test asserts that it holds the Mosaic kernel (``tpu_custom_call``).  This
is the check interpret mode cannot make — the compiler refuses block
shapes that are not tiling-aligned, and interpret mode accepts them.

The topology is described inside a module fixture, never at import, so
every test worker collects the same tests and only the worker running this
file loads the TPU compiler."""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get

CFG = get("phi4-mini-3.8b")
SLOTS, PAGE, MAX_BLOCKS = 4, 16, 40        # 4 slots x 640 tokens
NUM_PAGES = SLOTS * MAX_BLOCKS + 1


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # no TPU compiler to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _pools(sharding, kv_dtype):
    """Stacked head-major K/V pools (+ int8 scale pools) as shapes."""
    L, KV, hd = CFG.n_layers, CFG.n_kv_heads, CFG.head_dim
    dt = jnp.int8 if kv_dtype == "int8" else jnp.bfloat16
    pool = jax.ShapeDtypeStruct((L, NUM_PAGES, KV, PAGE, hd), dt,
                                sharding=sharding)
    if kv_dtype != "int8":
        return pool, None
    scale = jax.ShapeDtypeStruct((L, NUM_PAGES, KV, PAGE, 1), jnp.float32,
                                 sharding=sharding)
    return pool, scale


def _ints(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("pps", [1, 4])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_decode_compiles_for_v5e(one_chip, kv_dtype, pps):
    from repro.kernels.decode_attention.paged import (
        paged_decode_attention_fwd)
    pool, scale = _pools(one_chip, kv_dtype)
    q = jax.ShapeDtypeStruct((SLOTS, 1, CFG.n_heads, CFG.head_dim),
                             jnp.bfloat16, sharding=one_chip)

    def step(q, k, v, tbl, lens, layer, ks, vs):
        return paged_decode_attention_fwd(q, k, v, tbl, lens, layer,
                                          k_scale=ks, v_scale=vs,
                                          pages_per_step=pps)

    compiled = jax.jit(step).lower(
        q, pool, pool, _ints((SLOTS, MAX_BLOCKS), one_chip),
        _ints((SLOTS,), one_chip), _ints((), one_chip), scale,
        scale).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("pps", [1, 4])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_prefill_compiles_for_v5e(one_chip, kv_dtype, pps):
    from repro.kernels.decode_attention.prefill_paged import (
        paged_prefill_attention_fwd)
    T = 64
    pool, scale = _pools(one_chip, kv_dtype)
    q = jax.ShapeDtypeStruct((SLOTS, T, CFG.n_heads, CFG.head_dim),
                             jnp.bfloat16, sharding=one_chip)

    def step(q, k, v, tbl, base, new, layer, ks, vs):
        return paged_prefill_attention_fwd(q, k, v, tbl, base, new, layer,
                                           k_scale=ks, v_scale=vs,
                                           pages_per_step=pps)

    compiled = jax.jit(step).lower(
        q, pool, pool, _ints((SLOTS, MAX_BLOCKS), one_chip),
        _ints((SLOTS,), one_chip), _ints((SLOTS,), one_chip),
        _ints((), one_chip), scale, scale).compile()
    _assert_kernel(compiled)
