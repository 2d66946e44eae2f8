"""A configuration of another architecture is added by new files only: a
config file that names its own reference module is checked against that
module, by a run and by the fp8 control; a routed-expert configuration is
built through ``serve.build`` with the program's widths from its
``program_settings``, runs end to end, and a new metric reader reads its
published keys; and the committed configurations derive the
``ArchConfig`` they always did."""
import dataclasses
import json
import shutil
from pathlib import Path

import pytest

from bench import control, run, serve, spec
from bench.spec import ROOT, Sizes
from bench.tests import tiny

# A reference that is bench/reference.py and notes each call beside itself.
RECORDING_REFERENCE = '''
import json
from pathlib import Path

from bench import reference

CALLS = Path(__file__).with_suffix(".calls")


def logits(weights, sizes, tokens, rows, fp8=False):
    with CALLS.open("a") as f:
        f.write(json.dumps({"fp8": fp8}) + "\\n")
    return reference.logits(weights, sizes, tokens, rows, fp8=fp8)
'''

# The registry's grok-1 wiring at toy widths: 4 experts, top 2, each
# expert's width 32 in 2 blocks of 16.  The program's names for the widths
# come from program_settings and win over the published intermediate_size.
# capacity_factor 4.0 > E / k gives every expert room for every token of a
# dispatch, so nothing is dropped.
MOE_CONFIG = {
    "source": "test", "registry_id": "grok-1-314b",
    "reference": "bench/moe_reference.py",
    "program_settings": {"attention_impl": "pallas", "n_experts": 4,
                         "experts_per_token": 2, "ep_shards": 8, "d_ff": 32,
                         "capacity_factor": 4.0},
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_experts": 4, "num_experts_per_tok": 2,
    "vocab_size": 256, "tie_word_embeddings": False, "hidden_act": "gelu",
    "rope_theta": 10000.0, "rms_norm_eps": 1e-05, "reduced": [],
    "max_context": 256, "serving": tiny.CONFIG["serving"],
}

# The toy routed model's limit, between its readings on the CPU with
# bench/tests/moe_reference.py: program widest gaps 0-0.336 over 14 seeds
# (now and then the program's bf16 router and the float32 reference pick
# different experts at a near-tie), and 0.71-0.75 on 3 seeds with each
# expert's blocks credited to the next expert in the reference.
MOE_LIMIT = 0.6

# Published keys the dense fields do not hold, as a windowed routed-expert
# model's config.json gives them.
WINDOWED_MOE = {
    "num_hidden_layers": 4, "hidden_size": 2304, "num_attention_heads": 32,
    "num_key_value_heads": 4, "head_dim": 128, "intermediate_size": 7168,
    "moe_intermediate_size": 896, "num_experts": 64,
    "num_experts_per_tok": 8, "vocab_size": 98304,
    "tie_word_embeddings": False, "rms_norm_eps": 1e-06,
    "sliding_window": 1024,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                           "factor": 16},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "registry_id": "grok-1-314b", "max_context": 4096,
    "program_settings": {"n_experts": 64, "experts_per_token": 8,
                         "d_ff": 896},
    "serving": {"max_batch": 16},
}


@pytest.mark.parametrize("name", ["granite-8b-18l", "phi4-mini-3.8b"])
def test_committed_config_derives_the_same_arch_config(name):
    from repro import configs
    conf = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())
    want = dataclasses.replace(
        configs.get(conf["registry_id"]), attention_impl="pallas",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], d_head=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        tie_embeddings=conf["tie_word_embeddings"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]))
    assert dataclasses.asdict(serve.arch_config(conf)) == \
        dataclasses.asdict(want)
    assert "reference" not in conf
    assert spec.load_reference(conf).__name__ == "bench.reference"


def test_sizes_carry_every_published_key():
    sz = Sizes.from_config(WINDOWED_MOE)
    assert (sz.layers, sz.d_ff, sz.vocab, sz.tied) == (4, 7168, 98304, False)
    assert sz.rope_theta is None              # kept under rope_parameters
    assert sz.get("num_experts") == 64
    assert sz.get("sliding_window") == 1024
    assert sz.get("layer_types")[3] == "full_attention"
    assert sz.get("rope_parameters")["full_attention"]["rope_type"] == "yarn"
    assert sz.get("dense_width", 0) == 0
    for key in ("registry_id", "program_settings", "serving", "max_context",
                "intermediate_size"):
        assert sz.get(key) is None
    again = Sizes.from_config(dict(reversed(list(WINDOWED_MOE.items()))))
    assert hash(sz) == hash(again) and sz == again   # a static jit argument
    assert "rope_theta" not in sz.arch_overrides()


def test_program_settings_win_over_published_sizes():
    cfg = serve.arch_config(WINDOWED_MOE)
    assert (cfg.d_ff, cfg.n_experts, cfg.experts_per_token) == (896, 64, 8)
    assert (cfg.n_layers, cfg.d_model, cfg.vocab_size) == (4, 2304, 98304)
    assert cfg.rope_theta == 10000.0          # the registry's, not None


def _calls(root: Path):
    path = root / "bench" / "own_reference.calls"
    return [json.loads(x)["fp8"] for x in path.read_text().splitlines()]


def test_run_and_control_check_against_the_configs_own_reference(tmp_path):
    conf = dict(tiny.CONFIG, reference="bench/own_reference.py")
    root = tiny.make_root(tmp_path, config=conf)
    (root / "bench" / "own_reference.py").write_text(RECORDING_REFERENCE)
    out = run.run(tiny.args(seed=2**31 + 17, seconds=2.0), root=root,
                  chip=tiny.cpu_chip)
    assert out["correct"] is True
    assert _calls(root) and not any(_calls(root))
    (root / "bench" / "own_reference.calls").unlink()

    limits = {"logit_gap_max": {"limit": tiny.LIMIT}}
    loaded = dict(conf, name="tiny")
    r = control.readings(loaded, dict(tiny.MIX, prime_s=0.0), 2**33 + 1,
                         seconds=2.0, limits=limits,
                         reference=spec.load_reference(loaded, root))
    assert r["program"]["correct"] is True
    assert r["control"]["correct"] is False
    assert True in _calls(root) and False in _calls(root)


def test_missing_reference_module_is_a_spec_error(tmp_path):
    with pytest.raises(spec.SpecError):
        spec.load_reference(dict(tiny.CONFIG, reference="bench/nope.py"),
                            tmp_path)


def test_routed_expert_config_runs_end_to_end(tmp_path):
    root = tiny.make_root(tmp_path, limit=MOE_LIMIT, config=MOE_CONFIG,
                          tied=False)
    shutil.copy(Path(__file__).with_name("moe_reference.py"),
                root / "bench" / "moe_reference.py")
    (root / "bench" / "metrics" / "experts_per_layer.py").write_text(
        "def read(rec):\n    return float(rec.sizes.get('num_experts'))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["end_to_end"].append({"name": "experts_per_layer",
                                "unit": "experts", "better": "higher",
                                "bound": 0.01, "source": "host_clock",
                                "workloads": [tiny.CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cfg = serve.arch_config(MOE_CONFIG)
    assert (cfg.family, cfg.n_experts, cfg.d_ff) == ("moe", 4, 32)
    out = run.run(tiny.args(seed=2**31 + 29, seconds=2.0), root=root,
                  chip=tiny.cpu_chip)
    assert out["correct"] is True, out["checks"]
    assert out["metrics"]["experts_per_layer"]["value"] == 4.0
