"""Both paged Pallas kernels compile for a described TPU v5e at
phi4-mini-3.8b's published attention widths (24 heads / 8 KV heads, head
dim 128, 16-token pages), bf16 and int8 pools: the ragged prefill kernel
at one and four pages per grid step, the decode kernel at the page block
it derives, also at granite-8b's widths (32 / 8 heads) and at head dims
that are not a multiple of 128 lanes (qwen2-0.5b's 64, zamba2-7b's 112).
Nothing runs:
the TPU compiler is asked for the executable, and the test asserts that
it holds the Mosaic kernel (``tpu_custom_call``).  This is the check
interpret mode cannot make — the compiler refuses block shapes that are
not tiling-aligned, and interpret mode accepts them.

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


def _pools(sharding, kv_dtype, cfg=CFG):
    """Stacked head-major K/V pools (+ int8 scale pools) as shapes."""
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
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


@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "granite-8b"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_decode_compiles_for_v5e(one_chip, kv_dtype, arch):
    """The decode kernel at the page block it derives from the pool, at
    phi4-mini's (24/8 heads) and granite-8b's (32/8) widths."""
    _compile_decode(one_chip, kv_dtype, get(arch))


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "zamba2-7b"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_decode_compiles_for_v5e_at_narrow_heads(one_chip, kv_dtype,
                                                      arch):
    """The decode kernel's page copies at head dims that leave part of a
    128-lane row empty (qwen2-0.5b: 14/2 heads of 64, whose int8 scales
    fill 32 lanes of a page; zamba2-7b: 32/32 heads of 112)."""
    _compile_decode(one_chip, kv_dtype, get(arch))


def _compile_decode(sharding, kv_dtype, cfg):
    from repro.kernels.decode_attention.paged import (
        paged_decode_attention_fwd)
    pool, scale = _pools(sharding, kv_dtype, cfg)
    q = jax.ShapeDtypeStruct((SLOTS, 1, cfg.n_heads, cfg.head_dim),
                             jnp.bfloat16, sharding=sharding)

    def step(q, k, v, tbl, lens, layer, ks, vs):
        return paged_decode_attention_fwd(q, k, v, tbl, lens, layer,
                                          k_scale=ks, v_scale=vs)

    compiled = jax.jit(step).lower(
        q, pool, pool, _ints((SLOTS, MAX_BLOCKS), sharding),
        _ints((SLOTS,), sharding), _ints((), sharding), scale,
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


@pytest.mark.parametrize("lane", ["prefill", "decode"])
def test_engine_lanes_name_their_device_work_for_v5e(one_chip, monkeypatch,
                                                     lane):
    """The serving engine's two compiled cells at toy widths, compiled
    for the described v5e as its profiler sees them: the lane's module
    keeps its jit name, its Mosaic kernel is the custom call named after
    the Pallas kernel, and every matmul and every custom call the program
    put there carries exactly one of the model step's named scopes (the
    custom calls XLA adds, such as buffer allocations, carry no
    ``op_name`` at all)."""
    import dataclasses
    import re

    import numpy as np

    from repro.kernels.decode_attention import ops
    from repro.models import get_model
    from repro.serve.engine import PagedEngine, ServeConfig

    scopes = ("embed", "qkv", "kv_write", "attn", "out_proj", "ffn", "head",
              "sample")
    kernel = {"prefill": "ragged_prefill_attention",
              "decode": "paged_decode_attention"}[lane]
    cfg = dataclasses.replace(CFG, attention_impl="pallas", n_layers=2,
                              d_model=256, d_ff=512, vocab_size=512)
    model = get_model(cfg)
    eng = PagedEngine(model, model.init(jax.random.key(0)), ServeConfig(
        max_batch=SLOTS, max_seq=MAX_BLOCKS * PAGE, page_size=PAGE,
        num_pages=NUM_PAGES, prefill_chunk=4, prefill_chunk_tokens=64,
        trace_pool=False))
    monkeypatch.setattr(ops, "auto_interpret", lambda interpret=None: False)

    def shape(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(shape, eng.params)
    cache = jax.tree.map(shape, {"k": eng.kv.k, "v": eng.kv.v,
                                 "table": eng._table_dev,
                                 "length": eng._length_dev})
    key, grants = shape(eng.key), shape(np.zeros((SLOTS,), np.int32))
    if lane == "prefill":
        lowered = eng._prefill_lane.lower(
            params, shape(np.zeros((SLOTS, 64), np.int32)), cache, key,
            grants, temperature=0.0)
    else:
        lowered = eng._many_plain.lower(
            params, shape(np.zeros((SLOTS, 1), np.int32)), cache, key,
            grants, num_steps=4, temperature=0.0)
    text = lowered.compile().as_text()
    assert re.search(r"HloModule (\S+?),", text).group(1) == \
        f"jit_{lane}_many_paged"
    kernels = 0
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?(\S+) = .*? (custom-call|convolution|"
                     r"dot)\(", line)
        op = re.search(r'op_name="([^"]*)"', line)
        if not m or (m.group(2) == "custom-call" and not op):
            continue
        path = op.group(1).split("/") if op else []
        assert len([c for c in path if c in scopes]) == 1, line[:200]
        if "tpu_custom_call" in line:
            assert m.group(1).split(".")[0] == kernel
            assert "attn" in path
            kernels += 1
    assert kernels == 1                     # one kernel call in the scan body
