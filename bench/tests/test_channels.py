"""What a run hands the per-layer readers besides the window: the engine's
counters over the window and over its traced ticks, and the trace reduced
per named scope and kernel; a configuration's own scope and kernel names
read from a hand-made trace, its kernel kept out of a lane's attention
kernel time; and a configuration that names its own scopes and kernels,
with readers of a counter and a scope, entering the benchmark by new
files and entries alone."""
import contextlib
import json
from pathlib import Path

import pytest

from bench import record, run, serve, spans, spec, trace
from bench.spans import HostSpan
from bench.spec import ROOT
from bench.tests import tiny

MS = 1e6                                   # ns per ms
KERNEL = ', custom_call_target="tpu_custom_call"'
TRACE_NAMES = {"trace_scopes": ["moe_route", "moe_experts"],
               "trace_kernels": ["grouped_matmul"]}
SPAN_READERS = ("decode_dense_step_ms.lat", "decode_dense_step_ms.batch",
                "prefill_dense_ms.lat", "prefill_kv_write_ms.lat")


@contextlib.contextmanager
def watched(mp):
    """Keep the engine a run builds, the Record it hands the readers, and
    the engine's own ``tokens_out`` where the window opens, where its
    traced part opens and where the trace closes."""
    seen = {"records": []}
    build, arm, profiled = serve.build, run.CompileCounter.arm, run.profiled
    Record = record.Record

    def kept_build(conf, seed):
        out = build(conf, seed)
        seen["engine"] = out[0]
        return out

    def kept_arm(self):
        seen["open"] = seen["engine"].tokens_out
        arm(self)

    @contextlib.contextmanager
    def kept_profiled(log_dir, name):
        seen["traced"] = seen["engine"].tokens_out
        with profiled(log_dir, name):
            yield
        seen["close"] = seen["engine"].tokens_out

    def kept_record(*a, **k):
        seen["records"].append(Record(*a, **k))
        return seen["records"][-1]

    mp.setattr(serve, "build", kept_build)
    mp.setattr(run.CompileCounter, "arm", kept_arm)
    mp.setattr(run, "profiled", kept_profiled)
    mp.setattr(record, "Record", kept_record)
    yield seen


def _watched_run(tmp_path_factory, traced: int):
    root = tiny.make_root(tmp_path_factory.mktemp("channels"))
    with pytest.MonkeyPatch.context() as mp, watched(mp) as seen:
        seen["out"] = run.run(tiny.args(seed=2**31 + 41, seconds=3.0,
                                        trace=traced),
                              root=root, chip=tiny.cpu_chip)
    seen["rec"], = seen["records"]
    seen["trace"] = traced
    return seen


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    return _watched_run(tmp_path_factory, 1)


@pytest.fixture(scope="module")
def untraced(tmp_path_factory):
    return _watched_run(tmp_path_factory, 0)


@pytest.mark.parametrize("which", ["untraced", "traced"])
def test_record_carries_the_engines_counters(which, request):
    seen = request.getfixturevalue(which)
    rec = seen["rec"]
    eng = seen["engine"]
    assert seen["out"]["correct"] is True
    assert set(rec.counters) == set(eng.counters())     # every key
    close = eng.tokens_out          # cancelling at the close emits nothing
    assert rec.counters["tokens_out"] == close - seen["open"] > 0
    assert rec.counters["tokens_out"] == rec.window.tokens
    assert rec.counters["ticks"] == len(rec.window.ticks)
    if not seen["trace"]:
        assert rec.counters_traced == {} and rec.spans is None
        return
    assert seen["close"] == close
    a, b = rec.window.traced
    assert set(rec.counters_traced) == set(rec.counters)
    assert rec.counters_traced["tokens_out"] == close - seen["traced"] > 0
    assert rec.counters_traced["ticks"] == b - a
    assert rec.counters_traced["tokens_out"] < rec.counters["tokens_out"]


def test_traced_run_carries_spans_and_span_readers_read_nothing_there(
        traced):
    """The CPU trace holds no TPU plane: ``spans`` is there with no lane,
    and the readers of scopes return None rather than 0; the counter
    readers read the window's counters."""
    rec = traced["rec"]
    assert rec.spans["lanes"] == {} and rec.spans["window_s"] > 0
    for name in SPAN_READERS:
        assert spec.metric_reader(name)(rec) is None, name
    for name in ("decode_table_share.lat", "decode_table_share.batch"):
        assert 0 < spec.metric_reader(name)(rec) <= 100
    assert spec.metric_reader("recompute_share.batch")(rec) == \
        100.0 * rec.counters["recompute_tokens"] / sum(
            int(t.prefill.sum()) for t in rec.window.ticks
            if t.prefill is not None)


# A decode-lane module holding a routed layer's ops: the router and the
# expert matmul in a configuration's own scopes, the grouped matmul's
# Mosaic call, and the attention kernel's.
ROUTED_HLO = """
%routed_body.900 (p: bf16[8]) -> bf16[8] {
  %p = bf16[8]{0} parameter(0)
  %router_dot.901 = bf16[8]{0} multiply(%p, %p), metadata={op_name="jit(decode_many_paged)/while/body/moe_route/dot_general"}
  %expert_add.902 = bf16[8]{0} add(%p, %p), metadata={op_name="jit(decode_many_paged)/while/body/moe_experts/add"}
  %nested_mul.903 = bf16[8]{0} multiply(%p, %p), metadata={op_name="jit(decode_many_paged)/while/body/ffn/moe_experts/mul"}
  %grouped_matmul.904 = bf16[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode_many_paged)/while/body/moe_experts/pallas_call"}
  ROOT %paged_decode_attention.905 = bf16[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode_many_paged)/while/body/attn/pallas_call"}
}
"""


def routed_ops(w0: float):
    """A decode module run 1 ms into a window opening at ``w0``, with one
    1-ms op after another: router, experts, an op of ``ffn`` and
    ``moe_experts`` at once, the grouped matmul, the attention kernel."""
    mod = trace.Span("jit_decode_many_paged(1)", w0 + 1 * MS, w0 + 6 * MS)
    texts = ["%router_dot.901 = bf16[8]{0} multiply(%p, %p)",
             "%expert_add.902 = bf16[8]{0} add(%p, %p)",
             "%nested_mul.903 = bf16[8]{0} multiply(%p, %p)",
             "%grouped_matmul.904 = bf16[8]{0} custom-call(%p)" + KERNEL,
             "%paged_decode_attention.905 = bf16[8]{0} custom-call(%p)"
             + KERNEL]
    ops = [trace.Span(t, w0 + (1 + i) * MS, w0 + (2 + i) * MS)
           for i, t in enumerate(texts)]
    return mod, ops


def test_a_configs_scopes_and_kernels_are_read_from_a_hand_made_trace():
    mod, raw = routed_ops(0.0)
    window = HostSpan("traced_window", 0, 10 * MS)
    hlo = {"jit_decode_many_paged": ROUTED_HLO}
    modules = [HostSpan(mod.name, mod.start, mod.end)]

    def lane(scopes=(), kernels=()):
        ops = spans.build_ops(raw, modules, hlo, scopes)
        return spans.reduce(spans.Spans(ops, modules, [window], 1),
                            kernels)["lanes"]["decode"]

    plain = lane()                  # today's names only
    assert plain["scopes"] == {"ffn": pytest.approx(0.001),
                               "attn": pytest.approx(0.001)}
    assert plain["kernels"] == {"paged_decode_attention":
                                pytest.approx(0.001)}
    own = lane(TRACE_NAMES["trace_scopes"], TRACE_NAMES["trace_kernels"])
    # the op under both ffn and moe_experts takes no scope
    assert own["scopes"] == {"moe_route": pytest.approx(0.001),
                             "moe_experts": pytest.approx(0.002),
                             "attn": pytest.approx(0.001)}
    assert own["unscoped_s"] == pytest.approx(0.001)
    assert own["kernels"] == {"paged_decode_attention": pytest.approx(0.001),
                              "grouped_matmul": pytest.approx(0.001)}

    tr = trace.Trace(raw, [mod], [trace.Span("traced_window", 0, 10 * MS)],
                     chips=1)
    both = trace.reduce(tr)["lanes"]["decode"]
    red = trace.reduce(tr, other_kernels=TRACE_NAMES["trace_kernels"])
    attn = red["lanes"]["decode"]
    assert (both["kernel_s"], both["kernel_ops"]) == (pytest.approx(0.002), 2)
    assert (attn["kernel_s"], attn["kernel_ops"]) == (pytest.approx(0.001), 1)
    assert attn["module_s"] == both["module_s"] == pytest.approx(0.005)
    assert red["busy_s"] == pytest.approx(0.005)
    assert dict(red["device_ops"])[
        "jit_decode_many_paged:%grouped_matmul.904 custom-call"] == \
        pytest.approx(0.001)


@pytest.mark.parametrize("name", ["granite-8b-18l", "phi4-mini-3.8b"])
def test_committed_configs_keep_todays_names(name):
    conf = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())
    assert not set(TRACE_NAMES) & set(conf)


SCOPE_READER = '''
def read(rec):
    """Device ms of the decode lane's expert scope per traced tick."""
    d = rec.span_lane("decode")
    s = None if d is None else d["scopes"].get("moe_experts")
    ticks = rec.counters_traced.get("ticks")
    return None if not s or not ticks else 1e3 * s / ticks
'''
KERNEL_READER = '''
def read(rec):
    d = rec.span_lane("decode")
    k = None if d is None else d["kernels"].get("grouped_matmul")
    return None if k is None else 1e3 * k
'''
ATTN_READER = '''
def read(rec):
    d = rec.lane("decode")
    return None if d is None else 1e3 * d["kernel_s"]
'''


def _files(root: Path):
    return {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}


def test_a_config_with_its_own_names_enters_by_new_files(tmp_path,
                                                        monkeypatch):
    """A configuration listing ``trace_scopes`` and ``trace_kernels``, its
    cell and three readers (a scope over a counter, its kernel, the lane's
    attention kernel) are new files and entries.  The CPU trace holds no
    TPU plane, so a routed decode module run is added to it as the chip
    would record it, with its ops' HLO beside the lanes' own."""
    from repro.serve.engine import PagedEngine
    root = tiny.make_root(tmp_path)
    before = _files(root)
    old_bench = json.loads((root / "BENCHMARK.json").read_text())

    bench = json.loads((root / "BENCHMARK.json").read_text())
    (root / "bench" / "configs" / "tiny_routed.json").write_text(
        json.dumps(dict(tiny.CONFIG, **TRACE_NAMES)))
    (root / "bench" / "limits" / "tiny_routed.chat.json").write_text(
        json.dumps({"logit_gap_max": {"limit": tiny.LIMIT}}))
    readers = {"expert_ms_per_tick": SCOPE_READER,
               "grouped_matmul_ms": KERNEL_READER,
               "decode_attn_kernel_ms": ATTN_READER}
    for name, text in readers.items():
        (root / "bench" / "metrics" / f"{name}.py").write_text(text)
        bench["per_layer"].append({
            "name": name, "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "model step",
            "moves": "tpot_p90_ms", "workloads": ["tiny_routed.chat"]})
    bench["configs"].append({"name": "tiny_routed", "source": "test",
                             "file": "bench/configs/tiny_routed.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny_routed.chat",
                               "config": "tiny_routed",
                               "traffic": "tiny_chat", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    after = _files(root)
    assert {p: after[p] for p in before
            if p.name != "BENCHMARK.json"} == \
        {p: b for p, b in before.items() if p.name != "BENCHMARK.json"}
    for key, entries in old_bench.items():     # only entries appended
        new = bench[key]
        assert (new[:len(entries)] == entries if isinstance(entries, list)
                else new == entries), key

    load, cell_hlo = trace.load, PagedEngine.cell_hlo

    def load_with_routed_run(log_dir):
        tr = load(log_dir)
        w0 = next(s.start for s in tr.host if s.name == "traced_window")
        mod, ops = routed_ops(w0)
        return trace.Trace(tr.ops + ops, tr.modules + [mod], tr.host,
                           tr.chips)

    def hlo_with_routed_ops(self):
        out = cell_hlo(self)
        out["jit_decode_many_paged"] += ROUTED_HLO
        return out

    monkeypatch.setattr(trace, "load", load_with_routed_run)
    monkeypatch.setattr(PagedEngine, "cell_hlo", hlo_with_routed_ops)
    assert spec.Sizes.from_config(dict(tiny.CONFIG, **TRACE_NAMES)) == \
        spec.Sizes.from_config(tiny.CONFIG)     # the harness's own keys
    with watched(monkeypatch) as seen:
        args = tiny.args(seed=2**31 + 43, seconds=2.0, trace=1,
                         cell="tiny_routed.chat")
        out = run.run(args, root=root, chip=tiny.cpu_chip)
    assert out["correct"] is True
    got = out["metrics"]
    ticks = seen["records"][0].counters_traced["ticks"]
    assert got["expert_ms_per_tick"]["value"] == pytest.approx(2.0 / ticks)
    assert got["grouped_matmul_ms"]["value"] == pytest.approx(1.0)
    # the grouped matmul is no part of the lane's attention kernel time
    assert got["decode_attn_kernel_ms"]["value"] == pytest.approx(1.0)
