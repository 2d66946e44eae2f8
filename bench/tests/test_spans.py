"""The reading of the program's own names in a trace: ops' scopes from
their ``op_name`` or their module's HLO text, per-lane scope and kernel
time, and idle gaps put down to the innermost engine span; on a hand-made
trace, on a real profiler file written on the CPU, and on a small trace
recorded on a TPU v5e (data/spans_v5e.json).  ``bench/trace.py`` reads
both recorded v5e traces as it did before the program named its work."""
import json
from pathlib import Path

import pytest

from bench import spans, trace
from bench.spans import HostSpan, Op, Spans

MS = 1e6                                   # ns per ms
DATA = Path(__file__).parent / "data"

HLO = """HloModule jit_decode_many_paged, entry_computation_layout={}

%fused_computation.1 (p: bf16[8]) -> bf16[8] {
  %p = bf16[8]{0} parameter(0)
  ROOT %multiply.2 = bf16[8]{0} multiply(%p, %p), metadata={op_name="jit(decode_many_paged)/while/body/ffn/mul"}
}

%fused_computation.7 (p0: bf16[64,8], p1: s32[4,2], p2: bf16[4,8]) -> bf16[64,8] {
  %p0 = bf16[64,8]{1,0} parameter(0)
  %p1 = s32[4,2]{1,0} parameter(1)
  %p2 = bf16[4,8]{1,0} parameter(2)
  %transpose.8 = bf16[4,8]{1,0} transpose(%p2), dimensions={0,1}, metadata={op_name="jit(prefill_many_paged)/while/body/qkv/dot_general"}
  ROOT %scatter.9 = bf16[64,8]{1,0} scatter(%p0, %p1, %transpose.8), update_window_dims={1}
}

ENTRY %main.3 (a: bf16[8]) -> bf16[8] {
  %a = bf16[8]{0} parameter(0)
  %convolution.4 = bf16[8]{0} convolution(%a, %a), metadata={op_name="jit(decode_many_paged)/while/body/qkv/dot_general"}
  %fusion.5 = bf16[8]{0} fusion(%convolution.4), kind=kLoop, calls=%fused_computation.1
  %fusion.10 = bf16[64,8]{1,0} fusion(%a, %a, %a), kind=kCustom, calls=%fused_computation.7
  ROOT %copy.6 = bf16[8]{0} copy(%fusion.5)
}
"""


def test_scope_of_takes_exactly_one_scope():
    assert spans.scope_of("jit(f)/while/body/closed_call/attn/pallas") == \
        "attn"
    assert spans.scope_of("jit(f)/while/body/dynamic_slice") == ""
    assert spans.scope_of("jit(f)/qkv/ffn/add") == ""      # ambiguous


def test_hlo_scopes_reads_op_names_and_fused_computations():
    m = spans.hlo_scopes(HLO)
    assert m["convolution.4"] == "qkv"
    assert m["fusion.5"] == "ffn"          # its fused computation's scope
    assert m["copy.6"] == ""
    # XLA's rewrite of the prefill lane's row scatter keeps no op_name:
    # the fusion's root is the K/V row write, whatever fed its updates
    assert m["fusion.10"] == "kv_write"


def test_ops_take_their_module_and_scope_from_the_hlo():
    mod = HostSpan("jit_decode_many_paged(3)", 0, 10 * MS)
    other = HostSpan("jit__patch_rows(4)", 10 * MS, 12 * MS)
    raw = [trace.Span("%convolution.4 = bf16[8] convolution(bf16[8] %a)",
                      1 * MS, 2 * MS),
           trace.Span("%fusion.5 = bf16[8] fusion(bf16[8] %convolution.4)",
                      2 * MS, 3 * MS),
           trace.Span("%fusion.10 = (bf16[64,8], s32[]) fusion(%a), "
                      "kind=kCustom", 3 * MS, 4 * MS),
           trace.Span("%fusion.5 = s32[4] fusion(s32[4] %b)", 10 * MS,
                      11 * MS)]
    ops = spans.build_ops(raw, [mod, other], {"jit_decode_many_paged": HLO})
    assert [(o.name, o.opcode, o.module, o.scope) for o in ops] == [
        ("convolution.4", "convolution", mod.name, "qkv"),
        ("fusion.5", "fusion", mod.name, "ffn"),
        ("fusion.10", "fusion", mod.name, "kv_write"),   # tuple shape
        ("fusion.5", "fusion", other.name, "")]          # no HLO given
    assert ops[2].text == "%fusion.10 = (bf16[64,8], s32[]) fusion("


def _hand_made():
    host = [HostSpan("traced_window", 0, 100 * MS),
            HostSpan("engine.step", 5 * MS, 95 * MS, {"dispatches": 2}),
            HostSpan("engine.plan", 6 * MS, 10 * MS),
            HostSpan("engine.dispatch_prefill", 10 * MS, 12 * MS),
            HostSpan("engine.bookkeep", 82 * MS, 95 * MS, {"rids": "3 4"})]
    modules = [HostSpan("jit_prefill_many_paged(7)", 12 * MS, 42 * MS),
               HostSpan("jit_decode_many_paged(9)", 42 * MS, 82 * MS)]
    ops = [("fusion.1", 12, 20, "qkv", "fusion"),
           ("ragged_prefill_attention.3", 20, 36, "attn", "custom-call"),
           ("fusion.2", 36, 40, "kv_write", "fusion"),
           ("copy.4", 40, 42, "", "copy"),
           ("while.5", 42, 82, "", "while"),
           ("paged_decode_attention.6", 42, 72, "attn", "custom-call"),
           ("convolution.7", 72, 80, "ffn", "convolution"),
           ("fusion.8", 80, 82, "sample", "fusion")]
    lane = {n: modules[0 if a < 42 else 1].name for n, a, *_ in ops}
    ops = [Op(f"%{n} = bf16[8] {code}(" + (
        ' ... custom_call_target="tpu_custom_call"'
        if code == "custom-call" else ""), a * MS, b * MS, lane[n], scope)
        for n, a, b, scope, code in ops]
    return Spans(ops, modules, host, chips=1)


def test_reduce_lanes_scopes_kernels_and_idle_by_span():
    red = spans.reduce(_hand_made())
    pre, dec = red["lanes"]["prefill"], red["lanes"]["decode"]
    assert (pre["runs"], pre["module_s"]) == (1, pytest.approx(0.030))
    assert pre["scopes"] == {"qkv": pytest.approx(0.008),
                             "attn": pytest.approx(0.016),
                             "kv_write": pytest.approx(0.004)}
    assert pre["unscoped_s"] == pytest.approx(0.002)
    assert pre["unscoped_ops"] == {"copy": pytest.approx(0.002)}
    assert pre["kernels"] == {"ragged_prefill_attention":
                              pytest.approx(0.016)}
    assert pre["scoped_share"] == pytest.approx(28 / 30)
    # the scan's while op spans its body: no time of its own
    assert dec["kernels"] == {"paged_decode_attention": pytest.approx(0.03)}
    assert spans.dense_s(dec) == pytest.approx(0.010)       # ffn + sample
    assert dec["scoped_share"] == pytest.approx(1.0)
    # idle: 0-12 ms (middle 6 ms: engine.plan), 82-100 (middle 91 ms:
    # engine.bookkeep)
    assert red["idle_gaps_by_span"] == {
        "engine.bookkeep": pytest.approx(0.018),
        "engine.plan": pytest.approx(0.012)}
    assert red["engine_spans"]["engine.step"] == [1, pytest.approx(0.09)]


def test_idle_outside_every_engine_span_is_no_span():
    sp = _hand_made()
    sp.host = [h for h in sp.host if h.name == "traced_window"]
    red = spans.reduce(sp)
    assert red["idle_gaps_by_span"] == {spans.NO_SPAN: pytest.approx(0.03)}


def test_round_trip_through_json():
    sp = _hand_made()
    assert Spans.from_json(json.loads(json.dumps(sp.to_json()))) == sp


def test_load_reads_engine_spans_and_attributes(tmp_path):
    """A real profiler file written on the CPU: the engine's spans with
    their attributes beside the benchmark's own annotations."""
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("traced_window"):
        with jax.profiler.TraceAnnotation("tick"):
            with jax.profiler.TraceAnnotation("engine.admit", rids="1 2"):
                jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    sp = spans.load(str(tmp_path), {})
    assert sorted(s.name for s in sp.host) == ["engine.admit", "tick",
                                               "traced_window"]
    admit = next(s for s in sp.host if s.name == "engine.admit")
    assert admit.attrs == {"rids": "1 2"}
    assert sp.chips == 0            # the CPU backend: no TPU plane


def test_recorded_v5e_spans():
    """Four engine ticks of phi4-mini (2 of its 32 layers, full widths)
    traced on a TPU v5e, ops' scopes read from the lanes' compiled HLO:
    both kernels by name, each lane's device time almost all in the eight
    scopes, the decode cell's dense scopes plus its kernel within 5% of
    its module time, and every idle gap inside an engine span."""
    sp = Spans.from_json(json.loads((DATA / "spans_v5e.json").read_text()))
    red = spans.reduce(sp)
    pre, dec = red["lanes"]["prefill"], red["lanes"]["decode"]
    assert (pre["runs"], dec["runs"]) == (2, 3)
    assert set(pre["kernels"]) == {"ragged_prefill_attention"}
    assert set(dec["kernels"]) == {"paged_decode_attention"}
    assert set(pre["scopes"]) == set(spans.SCOPES) - {"sample"}
    assert set(dec["scopes"]) == set(spans.SCOPES)
    for lane in (pre, dec):
        assert lane["scoped_share"] > 0.95
        assert lane["kernels"][next(iter(lane["kernels"]))] == \
            pytest.approx(lane["scopes"]["attn"], rel=0.02)
    covered = spans.dense_s(dec) + dec["kernels"]["paged_decode_attention"]
    assert covered == pytest.approx(dec["module_s"], rel=0.05)
    gaps = red["idle_gaps_by_span"]
    assert gaps and spans.NO_SPAN not in gaps
    assert all(k.startswith(spans.ENGINE) for k in gaps)
    steps = [h.attrs for h in sp.host if h.name == "engine.step"]
    assert [a["dispatches"] for a in steps] == [2, 3, 2, 2]
    assert red["engine_spans"]["engine.readback"][0] == 4


@pytest.mark.parametrize("fixture,expected", [
    ("trace_v5e.json", {
        "window_s": 3.744025813, "busy_s": 3.725569643,
        "lanes": {"prefill": (2, 1.569233892, 0.967766857, 64),
                  "decode": (2, 2.156302025, 2.056579687, 256)},
        "idle_by_host": {"tick": 0.015618166, "tick/plan": 0.002838004}}),
    ("spans_v5e.json", {
        "window_s": 0.286136579, "busy_s": 0.265745552,
        "lanes": {"prefill": (2, 0.074151597, 0.034911712, 4),
                  "decode": (3, 0.191584646, 0.163579774, 24)},
        "idle_by_host": {"tick": 0.020391027}}),
])
def test_trace_reduce_reads_the_recorded_traces_as_before(fixture, expected):
    """``trace.reduce``'s numbers on both recorded v5e traces, as the
    benchmark's reduction gave them before this reader existed."""
    data = json.loads((DATA / fixture).read_text())
    if fixture == "trace_v5e.json":
        tr = trace.Trace.from_json(data)
    else:                           # the same trace as bench/trace.py reads
        sp = Spans.from_json(data)
        tr = trace.Trace(
            [trace.Span(o.text, o.start, o.end) for o in sp.ops],
            [trace.Span(m.name, m.start, m.end) for m in sp.modules],
            [trace.Span(h.name, h.start, h.end) for h in sp.host
             if h.name in trace.HOST_SPANS], sp.chips)
    red = trace.reduce(tr)
    assert set(red) == {"window_s", "busy_s", "lanes", "device_ops",
                        "idle_gaps", "idle_by_host"}
    assert red["window_s"] == pytest.approx(expected["window_s"])
    assert red["busy_s"] == pytest.approx(expected["busy_s"])
    assert {k: (v["runs"], pytest.approx(v["module_s"]),
                pytest.approx(v["kernel_s"]), v["kernel_ops"])
            for k, v in red["lanes"].items()} == expected["lanes"]
    assert red["idle_by_host"] == pytest.approx(expected["idle_by_host"])
