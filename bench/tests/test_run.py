"""The harness end to end on the CPU at toy widths (tiny.py): a run's
result line, the metrics it reports with and without a trace, the
refusal to run without a TPU, and a configuration, a traffic mix and a
metric added as new files being picked up by name alone."""
import json
import os
import subprocess
import sys

import pytest

from bench import run
from bench.peaks import UnknownDevice, for_kind
from bench.spec import ROOT
from bench.tests import tiny


@pytest.fixture(scope="module")
def result(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("tiny"))
    return run.run(tiny.args(seed=2**31 + 11, seconds=3.0), root=root,
                   chip=tiny.cpu_chip)


def test_result_line(result):
    assert list(result)[-1] == "checks"
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms",
                                      "setup_s"}
    for m in result["metrics"].values():
        assert m["value"] > 0
    gap = result["checks"]["logit_gap_max"]
    assert 0 <= gap["value"] <= gap["limit"]
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    json.dumps(result)


def test_closed_loop_cell_reports_its_own_metrics(tmp_path):
    """The closed-loop cell: clients part-way through their first request
    at the window's opening, the rate of tokens over the window, and the
    served tokens of the primed requests checked too."""
    root = tiny.make_root(tmp_path)
    out = run.run(tiny.args(seed=2**31 + 3, seconds=3.0,
                            cell=tiny.BATCH_CELL), root=root,
                  chip=tiny.cpu_chip)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"output_tok_s", "setup_s"}
    assert out["metrics"]["output_tok_s"]["value"] > 0
    traced = run.run(tiny.args(seed=6, seconds=3.0, trace=1,
                               cell=tiny.BATCH_CELL), root=root,
                     chip=tiny.cpu_chip)
    got = set(traced["metrics"])
    assert {"slot_occupancy.batch", "tick_host_ms.batch"} <= got
    assert 0 < traced["metrics"]["slot_occupancy.batch"]["value"] <= 100
    assert not got & {"ttft_p90_ms", "queue_wait_p90_ms",
                      "tick_host_ms.lat", "decode_step_ms.batch",
                      "step_mfu.batch", "decode_attn_roofline.batch"}


def test_traced_run_reports_per_layer_metrics(tmp_path):
    root = tiny.make_root(tmp_path)
    out = run.run(tiny.args(seed=5, seconds=3.0, trace=1), root=root,
                  chip=tiny.cpu_chip)
    assert out["correct"] is True
    got = set(out["metrics"])
    # host counters are read everywhere; the CPU trace holds no TPU plane,
    # so the device-trace readers return nothing rather than a zero
    assert {"queue_wait_p90_ms", "prefix_hit_share",
            "tick_host_ms.lat"} <= got
    assert not got & {"slot_occupancy.batch", "tick_host_ms.batch"}
    assert not got & {"prefill_attn_roofline", "decode_attn_roofline",
                      "prefill_step_mfu", "decode_step_mfu",
                      "prefill_step_ms.lat", "decode_step_ms.lat"}
    assert out["device"]["window_s"] > 0
    assert "breakdown" in out


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root = tiny.make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    conf = dict(tiny.CONFIG, num_key_value_heads=4)
    (root / "bench" / "configs" / "tiny_mha.json").write_text(
        json.dumps(conf))
    mix = dict(tiny.MIX, turns={"dist": "fixed", "value": 1}, prime_s=0.0)
    (root / "bench" / "traffic" / "one_turn.json").write_text(json.dumps(mix))
    (root / "bench" / "metrics" / "requests_due.py").write_text(
        "def read(rec):\n    return float(len(rec.requests))\n")
    (root / "bench" / "limits" / "tiny_mha.one_turn.json").write_text(
        json.dumps({"logit_gap_max": {"limit": tiny.LIMIT}}))
    bench["configs"].append({"name": "tiny_mha", "source": "test",
                             "file": "bench/configs/tiny_mha.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny_mha.one_turn",
                               "config": "tiny_mha", "traffic": "one_turn",
                               "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "requests_due", "unit": "requests",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["tiny_mha.one_turn"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    args = tiny.args(seed=9, seconds=2.0)
    args.workload = "tiny_mha.one_turn"
    out = run.run(args, root=root, chip=tiny.cpu_chip)
    assert out["correct"] is True
    assert out["metrics"]["requests_due"]["value"] == out["attempted"]


def test_no_tpu_exits_nonzero_without_a_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", "granite-8b-18l.code_ctx", "--seed",
                        "1", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_unknown_device_kind_is_an_error():
    with pytest.raises(UnknownDevice):
        for_kind("TPU v99")
    assert for_kind("TPU v5 lite").bf16_flops == 197e12
