"""A tiny copy of the benchmark for CPU tests: the real readers and
traffic generator, a two-layer configuration at toy widths, and a chip
stand-in that hands the harness the CPU device under the v5e's kind."""
from __future__ import annotations

import argparse
import json
import shutil
from pathlib import Path

from bench.peaks import for_kind
from bench.spec import ROOT

CONFIG = {
    "source": "test", "registry_id": "phi4-mini-3.8b",
    "program_settings": {"attention_impl": "pallas"},
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "vocab_size": 256, "tie_word_embeddings": True, "hidden_act": "silu",
    "rope_theta": 10000.0, "rms_norm_eps": 1e-05, "reduced": [],
    "max_context": 256,
    "serving": {"max_batch": 4, "page_size": 16, "num_pages": 65,
                "prefill_chunk": 4, "prefill_chunk_tokens": 32,
                "trace_pool": False},
}

MIX = {
    "loop": "open", "sessions_per_s": 6.0, "prime_s": 1.0,
    "turns": {"dist": "geometric", "mean": 2.0, "lo": 1, "hi": 3},
    "think_s": {"dist": "exponential", "mean": 0.2},
    "prefixes": {"count": 3, "zipf_s": 1.0,
                 "length": {"dist": "uniform", "lo": 21, "hi": 61}},
    "user": {"dist": "lognormal", "median": 12, "sigma": 0.8, "lo": 4,
             "hi": 40},
    "output": {"dist": "lognormal", "median": 8, "sigma": 0.5, "lo": 2,
               "hi": 24},
    "max_context": 256,
}

BATCH = {
    "loop": "closed", "clients": 6, "requests_per_client": 30,
    "prime_s": 1.0,
    "user": {"dist": "lognormal", "median": 24, "sigma": 0.6, "lo": 8,
             "hi": 64},
    "output": {"dist": "uniform", "lo": 8, "hi": 24},
}

CELL = "tiny.chat"
BATCH_CELL = "tiny.batch"
# the benchmark's cells that the tiny cells stand in for, by loop
STANDS_FOR = {"open": CELL, "closed": BATCH_CELL}
# between the toy cell's readings on the CPU: program widest gaps 0.002-0.005,
# fp8 control 0.050-0.080 (bench/tests/test_control.py)
LIMIT = 0.02


def make_root(tmp: Path, limit: float = LIMIT, config=None, mix=None,
              tied=True) -> Path:
    """A checkout-shaped directory: BENCHMARK.json naming a tiny open-loop
    cell and a tiny closed-loop cell, each with the real metrics of the
    benchmark's cells of its kind, and bench/ with the real readers."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = dict(config or CONFIG, tie_word_embeddings=tied)
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir()
    (tmp / "bench" / "limits").mkdir()
    shutil.copytree(ROOT / "bench" / "metrics", tmp / "bench" / "metrics")
    (tmp / "bench" / "configs" / "tiny.json").write_text(json.dumps(conf))
    (tmp / "bench" / "traffic" / "tiny_chat.json").write_text(
        json.dumps(mix or MIX))
    (tmp / "bench" / "traffic" / "tiny_batch.json").write_text(
        json.dumps(BATCH))
    for cell in (CELL, BATCH_CELL):
        (tmp / "bench" / "limits" / f"{cell}.json").write_text(
            json.dumps({"logit_gap_max": {"limit": limit}}))
    tiny_of = {}
    for w in bench["workloads"]:
        loop = json.loads((ROOT / "bench" / "traffic" /
                           f"{w['traffic']}.json").read_text())["loop"]
        tiny_of[w["name"]] = STANDS_FOR[loop]
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [{"name": CELL, "config": "tiny",
                           "traffic": "tiny_chat", "chips": 1,
                           "why": "test"},
                          {"name": BATCH_CELL, "config": "tiny",
                           "traffic": "tiny_batch", "chips": 1,
                           "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({tiny_of[c] for c in m["workloads"]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def cpu_chip(chips: int):
    import jax
    return jax.devices()[:chips], for_kind("TPU v5 lite")


def args(seed=7, seconds=3.0, trace=0, cell=CELL):
    return argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=trace)
