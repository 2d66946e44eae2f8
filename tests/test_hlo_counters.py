"""Census correctness against compiled XLA programs with known costs.

These compile tiny jitted functions on the single CPU device (and an 8-host
device subprocess-free collective case is covered in test_sharding.py) and
assert the parsed flops / bytes / issues / trip-count handling match
hand-computed values.
"""
import math

import jax
import jax.numpy as jnp
import pytest

from repro.core.hlo_counters import (
    Census, Shape, census_from_compiled, classify, parse_module,
    parse_shapes)


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


# ---------------------------------------------------------------------------
# unit parsing
# ---------------------------------------------------------------------------

def test_parse_shapes_simple():
    (s,) = parse_shapes("bf16[32,256]{1,0}")
    assert s.dtype == "bf16" and s.dims == (32, 256)
    assert s.bytes == 32 * 256 * 2


def test_parse_shapes_tuple_and_scalar():
    shapes = parse_shapes("(f32[2,3]{1,0}, s32[], pred[7])")
    assert [s.dtype for s in shapes] == ["f32", "s32", "pred"]
    assert shapes[1].dims == ()
    assert shapes[2].bytes == 7


def test_vreg_padding():
    # (8,128) exactly one vreg
    assert Shape("f32", (8, 128)).padded_vreg_issues() == 1
    # minor dims padded: (1,1) still one issue
    assert Shape("f32", (1, 1)).padded_vreg_issues() == 1
    # (16, 200): 2 sublanes-groups x 2 lane-groups
    assert Shape("f32", (16, 200)).padded_vreg_issues() == 4
    # leading dims multiply
    assert Shape("f32", (3, 8, 128)).padded_vreg_issues() == 3
    # rank-1
    assert Shape("f32", (257,)).padded_vreg_issues() == 3


def test_classify():
    assert classify("dot") == "mxu"
    assert classify("all-reduce") == "collective"
    assert classify("all-reduce-start") == "collective"
    assert classify("copy") == "layout"
    assert classify("gather") == "irregular"
    assert classify("add") == "vpu"
    assert classify("while") == "flow"
    assert classify("parameter") == "none"


# ---------------------------------------------------------------------------
# compiled-program census
# ---------------------------------------------------------------------------

def test_matmul_flops_exact():
    M, K, N = 128, 256, 512

    def f(a, b):
        return a @ b

    compiled = _compile(f, jax.ShapeDtypeStruct((M, K), jnp.float32),
                        jax.ShapeDtypeStruct((K, N), jnp.float32))
    census = census_from_compiled(compiled)
    assert census.mxu_flops == pytest.approx(2 * M * K * N)
    # aligned shapes: exact tile count, no padding waste
    assert census.mxu_issues == (M // 128) * (N // 128) * (K // 128)
    assert census.mxu_flops_padded == pytest.approx(census.mxu_flops)
    # bytes: read a + b, write out (fusion-boundary model)
    expect = 4 * (M * K + K * N + M * N)
    assert census.hbm_bytes == pytest.approx(expect, rel=0.05)


def test_matmul_padding_waste_visible():
    """head_dim-64-style contraction: FLOP census halves, issue census does
    not — the padding-efficiency readout must expose it."""
    def f(a, b):
        return a @ b

    compiled = _compile(f, jax.ShapeDtypeStruct((128, 64), jnp.float32),
                        jax.ShapeDtypeStruct((64, 128), jnp.float32))
    census = census_from_compiled(compiled)
    assert census.mxu_issues == 1          # one padded pass
    assert census.mxu_flops == pytest.approx(2 * 128 * 64 * 128)
    assert census.mxu_flops / census.mxu_flops_padded == pytest.approx(0.5)


def test_scan_trip_count_scaling():
    """cost_analysis counts a while body once; the census must scale by the
    known_trip_count backend config."""
    L, D = 7, 64

    def body(h, w):
        return jnp.tanh(h @ w), None

    def f(h, ws):
        h, _ = jax.lax.scan(body, h, ws)
        return h

    compiled = _compile(f, jax.ShapeDtypeStruct((D, D), jnp.float32),
                        jax.ShapeDtypeStruct((L, D, D), jnp.float32))
    census = census_from_compiled(compiled)
    assert census.mxu_flops == pytest.approx(L * 2 * D * D * D)
    # XLA's own analysis sees one iteration:
    ca = compiled.cost_analysis()
    assert ca["flops"] < census.mxu_flops / 2


def test_scan_weight_bytes_slice_aware():
    """Each scan iteration must charge one layer's weights, not the whole
    stacked buffer (slice-aware fusion reads)."""
    L, D = 10, 128

    def body(h, w):
        return jnp.tanh(h @ w), None

    def f(h, ws):
        h, _ = jax.lax.scan(body, h, ws)
        return h

    compiled = _compile(f, jax.ShapeDtypeStruct((D, D), jnp.float32),
                        jax.ShapeDtypeStruct((L, D, D), jnp.float32))
    census = census_from_compiled(compiled)
    weights_once = L * D * D * 4
    # total traffic should be O(L * (one-layer-slice + activations)) — about
    # 5.4 MB here — far below charging L x the full stacked buffer (>10 MB)
    assert census.hbm_bytes < 9 * weights_once
    assert census.hbm_bytes > weights_once          # reads each layer once


def test_elementwise_census():
    N = 8 * 128 * 4

    def f(a, b):
        return a * b + 1.0

    compiled = _compile(f, jax.ShapeDtypeStruct((N,), jnp.float32),
                        jax.ShapeDtypeStruct((N,), jnp.float32))
    census = census_from_compiled(compiled)
    assert census.mxu_flops == 0
    assert census.vpu_flops >= 2 * N                # mul + add
    assert census.hbm_bytes >= 3 * N * 4            # 2 reads 1 write


def test_reduce_census():
    def f(a):
        return a.sum()

    compiled = _compile(f, jax.ShapeDtypeStruct((64, 256), jnp.float32))
    census = census_from_compiled(compiled)
    assert census.vpu_flops >= 64 * 256
    assert census.scalar_ops >= 0


def test_census_total_instructions_positive():
    def f(a, b):
        return jnp.dot(a, b).sum()

    compiled = _compile(f, jax.ShapeDtypeStruct((32, 32), jnp.float32),
                        jax.ShapeDtypeStruct((32, 32), jnp.float32))
    census = census_from_compiled(compiled)
    assert census.total_instructions > 0
    assert census.mxu_issues == 1


# ---------------------------------------------------------------------------
# synthetic-text collectives (real multi-device case in test_sharding.py)
# ---------------------------------------------------------------------------

SYNTH = """
HloModule synth, is_scheduled=true, entry_computation_layout={(f32[128,128])->f32[128,128]}, num_partitions=8

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main_spmd (param: f32[128,128]) -> f32[128,128] {
  %param = f32[128,128]{1,0} parameter(0)
  %ar = f32[128,128]{1,0} all-reduce(%param), channel_id=1, replica_groups=[2,4]<=[8], use_global_device_ids=true, to_apply=%add
  %ag = f32[128,128]{1,0} all-gather(%ar), channel_id=2, replica_groups=[4,2]<=[8], dimensions={0}, use_global_device_ids=true
  ROOT %cp = f32[128,128]{1,0} collective-permute(%ag), channel_id=3, source_target_pairs={{0,1},{1,0}}
}
"""


SYNTH_S8_BRACKET = """
HloModule synth_s8, is_scheduled=true, entry_computation_layout={(s8[4,64,16],f32[1,1,16],s32[])->s8[4,64,16]}

ENTRY %main (pool: s8[4,64,16], row: f32[1,1,16], i: s32[]) -> s8[4,64,16] {
  %pool = s8[4,64,16]{2,1,0} parameter(0)
  %row = f32[1,1,16]{2,1,0} parameter(1)
  %i = s32[] parameter(2)
  %c0 = s32[] constant(0)
  %up = f32[4,64,16]{2,1,0} convert(%pool)
  %dus = f32[4,64,16]{2,1,0} dynamic-update-slice(%up, %row, %c0, %i, %c0)
  ROOT %down = s8[4,64,16]{2,1,0} convert(%dus)
}
"""

SYNTH_S8_ONEWAY = """
HloModule synth_s8_oneway, is_scheduled=true, entry_computation_layout={(s8[4,64,16],f32[1,1,16],s32[])->f32[4,64,16]}

ENTRY %main (pool: s8[4,64,16], row: f32[1,1,16], i: s32[]) -> f32[4,64,16] {
  %pool = s8[4,64,16]{2,1,0} parameter(0)
  %row = f32[1,1,16]{2,1,0} parameter(1)
  %i = s32[] parameter(2)
  %c0 = s32[] constant(0)
  %up = f32[4,64,16]{2,1,0} convert(%pool)
  ROOT %dus = f32[4,64,16]{2,1,0} dynamic-update-slice(%up, %row, %c0, %i, %c0)
}
"""


def test_s8_dtype_bracket_elision_matched_pair():
    """The dtype-bracket matcher is narrow-dtype generic: an s8->f32
    upcast straight off a parameter paired with a same-shape f32->s8
    downcast at the root (the shape a backend without native s8 scatter
    would emit around a quantized-pool update) is elided — BOTH converts,
    nothing else."""
    from repro.core.hlo_counters import (_dtype_bracket_elisions,
                                         parse_module)
    comps, entry = parse_module(SYNTH_S8_BRACKET)
    elide = _dtype_bracket_elisions(comps[entry], comps)
    assert elide == {"up", "down"}
    # and the census actually drops their whole-pool bytes: only the
    # update slice + row traffic remains, not 2x the f32 pool
    from repro.core.hlo_counters import census_from_text
    census = census_from_text(SYNTH_S8_BRACKET)
    pool_f32 = 4 * 64 * 16 * 4
    assert census.hbm_bytes < 2 * pool_f32


def test_s8_one_way_cast_still_counted():
    """A genuine one-way s8->f32 upcast (dequantization for compute, no
    same-shape downcast partner) must STAY counted — eliding it would hide
    real dequant traffic from the quantized-pool byte model."""
    from repro.core.hlo_counters import (_dtype_bracket_elisions,
                                         census_from_text, parse_module)
    comps, entry = parse_module(SYNTH_S8_ONEWAY)
    assert _dtype_bracket_elisions(comps[entry], comps) == set()
    census = census_from_text(SYNTH_S8_ONEWAY)
    pool_s8 = 4 * 64 * 16
    # the convert reads the s8 pool and writes the f32 copy at minimum
    assert census.hbm_bytes >= 5 * pool_s8


def test_int8_pool_update_census_pool_independent():
    """Compiled-program regression for the quantized append: an in-place
    int8 row update (the quantize write path's pool op) moves bytes
    independent of the POOL size on this backend — whether the lowering
    scatters s8 natively (CPU today) or brackets in converts (elided)."""
    def upd(pool, row, p):
        return pool.at[0, p, 3].set(row)

    def census(P):
        pool = jax.ShapeDtypeStruct((2, P, 8, 2, 16), jnp.int8)
        row = jax.ShapeDtypeStruct((2, 16), jnp.int8)
        c = jax.jit(upd, donate_argnums=(0,)).lower(
            pool, row, jax.ShapeDtypeStruct((), jnp.int32)).compile()
        return census_from_compiled(c)

    small, big = census(33), census(65)
    assert big.hbm_bytes == small.hbm_bytes
    assert small.hbm_bytes < 2 * 8 * 2 * 16 * 4   # a page's worth, not a pool


def test_synthetic_collective_census():
    from repro.core.hlo_counters import census_from_text
    census = census_from_text(SYNTH)
    b = 128 * 128 * 4
    ar = census.collectives["all-reduce"]
    ag = census.collectives["all-gather"]
    cp = census.collectives["collective-permute"]
    assert ar.count == 1 and ag.count == 1 and cp.count == 1
    assert ar.wire_bytes == pytest.approx(2 * b * 3 / 4)   # group size 4
    assert ag.wire_bytes == pytest.approx(b * 1 / 2)       # group size 2
    assert cp.wire_bytes == pytest.approx(b)
    assert census.collective_wire_bytes == pytest.approx(
        2 * b * 3 / 4 + b / 2 + b)
