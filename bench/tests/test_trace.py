"""The trace reduction: busy and idle time, lane and kernel attribution by
XLA module, and idle gaps named by the host span open across them; on a
hand-made trace, on a small trace recorded on a TPU v5e (data/), and the
reading of a real profiler file."""
import json
from pathlib import Path

import pytest

from bench import trace
from bench.trace import Span, Trace

DATA = Path(__file__).parent / "data" / "trace_v5e.json"
MS = 1e6                                   # ns per ms


def _hand_made():
    host = [Span("traced_window", 0, 100 * MS),
            Span("tick", 10 * MS, 50 * MS), Span("plan", 10 * MS, 12 * MS),
            Span("tick", 60 * MS, 90 * MS)]
    modules = [Span("jit_prefill_many_paged(7)", 20 * MS, 40 * MS),
               Span("jit__lambda(9)", 70 * MS, 80 * MS)]
    kern = ' custom-call(...), custom_call_target="tpu_custom_call"'
    ops = [Span("%fusion.1 = bf16[8] fusion(x)", 20 * MS, 30 * MS),
           Span("%closed_call.3 = bf16[8]" + kern, 30 * MS, 40 * MS),
           Span("%closed_call.5 = bf16[8]" + kern, 70 * MS, 75 * MS),
           Span("%custom-call.6 = s32[4] custom-call(), custom_call_target"
                '="AllocateBuffer"', 75 * MS, 76 * MS),
           Span("%fusion.2 = bf16[8] fusion(y)", 74 * MS, 80 * MS),
           Span("%while.9 = (s32[]) while(z)", 70 * MS, 80 * MS)]
    return Trace(ops, modules, host, chips=1)


def test_busy_idle_and_lanes():
    red = trace.reduce(_hand_made())
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.03)        # 20 + 10 ms, merged
    pre, dec = red["lanes"]["prefill"], red["lanes"]["decode"]
    assert (pre["runs"], pre["module_s"], pre["kernel_s"]) == \
        (1, pytest.approx(0.02), pytest.approx(0.01))
    assert (dec["runs"], dec["module_s"], dec["kernel_s"]) == \
        (1, pytest.approx(0.01), pytest.approx(0.005))
    gaps = dict((round(s, 6), label) for label, s in red["idle_gaps"])
    # 0-20 ms: the tick opened at 10 ms -> the gap's middle is in "tick";
    # 40-70: middle 55 ms between ticks; 80-100: middle 90 ms, tick closed
    assert gaps[0.02] in ("tick", "between_ticks")
    assert gaps[0.03] == "between_ticks"
    assert sum(red["idle_by_host"].values()) == pytest.approx(0.07)
    # ops are attributed to the module run holding them; the while loop
    # that spans its body counts towards busy time but not per op
    top = dict(red["device_ops"])
    assert top["jit_prefill_many_paged:%closed_call.3 custom-call"] == \
        pytest.approx(0.01)
    assert not any("while" in k for k in top)


def test_a_lambda_without_a_kernel_is_no_lane():
    """Another jitted lambda (the page cache's compaction gather) shares
    the decode cell's jit name but holds no Mosaic kernel: its runs stay
    out of the decode lane."""
    tr = _hand_made()
    tr.modules.append(Span("jit__lambda(4)", 82 * MS, 88 * MS))
    tr.ops.append(Span("%gather.1 = bf16[8] gather(p)", 82 * MS, 88 * MS))
    red = trace.reduce(tr)
    dec = red["lanes"]["decode"]
    assert (dec["runs"], dec["module_s"]) == (1, pytest.approx(0.01))
    assert red["busy_s"] == pytest.approx(0.036)


def test_round_trip_through_json():
    tr = _hand_made()
    assert trace.Trace.from_json(json.loads(json.dumps(tr.to_json()))) == tr


def test_recorded_v5e_trace():
    """Two ticks of the phi4-mini chat cell traced on a TPU v5e
    (pages_per_step 1; op names cut after their opcode and Mosaic target):
    each lane's module runs hold exactly one Pallas kernel per layer and
    scan step, and the device was busy through nearly all of it."""
    tr = trace.Trace.from_json(json.loads(DATA.read_text()))
    red = trace.reduce(tr)
    assert 0 < red["busy_s"] <= red["window_s"]
    for name, lane in red["lanes"].items():
        assert lane["runs"] >= 1
        assert 0 < lane["kernel_s"] < lane["module_s"]
    assert red["lanes"]["prefill"]["kernel_ops"] == 32 * 2   # 32 layers
    assert red["lanes"]["decode"]["kernel_ops"] == 32 * 4 * 2  # x 4 steps
    assert red["busy_s"] > 0.99 * red["window_s"]


def test_load_reads_host_annotations(tmp_path):
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("traced_window"):
        with jax.profiler.TraceAnnotation("tick"):
            jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.load(str(tmp_path))
    names = sorted(s.name for s in tr.host)
    assert names == ["tick", "traced_window"]
    assert tr.chips == 0            # the CPU backend: no TPU plane
