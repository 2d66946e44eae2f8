"""Where the benchmark's pieces live, found by the names in
``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, so a later change adds a cell
by adding files and entries, never by editing one:

  * ``BENCHMARK.json`` ``configs[].file``      — model sizes + serving settings
  * ``bench/traffic/<traffic>.json``           — a traffic mix (parameters)
  * ``bench/metrics/<metric>.py``              — one per-layer metric reader

Nothing here imports JAX.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

ROOT = Path(__file__).resolve().parents[1]      # the checkout root


class SpecError(RuntimeError):
    """A name in BENCHMARK.json does not resolve to its file."""


def load_benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"{path} is missing")
    return json.loads(path.read_text())


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SpecError(f"no workload {name!r} in BENCHMARK.json; have "
                    f"{[c['name'] for c in bench['workloads']]}")


def load_config(bench: dict, cell: dict, root: Path = ROOT) -> dict:
    for conf in bench["configs"]:
        if conf["name"] == cell["config"]:
            data = json.loads((root / conf["file"]).read_text())
            data["name"] = conf["name"]
            return data
    raise SpecError(f"workload {cell['name']!r} names config "
                    f"{cell['config']!r}, which BENCHMARK.json lacks")


def load_mix(traffic: str, root: Path = ROOT) -> dict:
    path = root / "bench" / "traffic" / f"{traffic}.json"
    if not path.is_file():
        raise SpecError(f"traffic mix {traffic!r} has no file {path}")
    return json.loads(path.read_text())


def cell_metrics(bench: dict, cell: str, kind: str) -> List[dict]:
    """The ``kind`` ("end_to_end" or "per_layer") metrics this cell
    reports: those without a ``workloads`` list, and those whose list
    names the cell."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


Reader = Callable[[object], Optional[float]]


def metric_reader(name: str, root: Path = ROOT) -> Reader:
    """``read(record)`` from ``bench/metrics/<name>.py``.  A reader that
    finds nothing to read returns None."""
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"per-layer metric {name!r} has no reader {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The published sizes of a dense GQA decoder, as a config file holds
    them (Hugging Face key names)."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    rope_theta: float
    norm_eps: float

    @classmethod
    def from_config(cls, conf: dict) -> "Sizes":
        return cls(layers=conf["num_hidden_layers"],
                   d_model=conf["hidden_size"],
                   heads=conf["num_attention_heads"],
                   kv_heads=conf["num_key_value_heads"],
                   head_dim=conf["head_dim"],
                   d_ff=conf["intermediate_size"],
                   vocab=conf["vocab_size"],
                   tied=conf["tie_word_embeddings"],
                   rope_theta=float(conf["rope_theta"]),
                   norm_eps=float(conf["rms_norm_eps"]))

    def arch_overrides(self) -> Dict[str, object]:
        """The same sizes under the program's ``ArchConfig`` field names."""
        return dict(n_layers=self.layers, d_model=self.d_model,
                    n_heads=self.heads, n_kv_heads=self.kv_heads,
                    d_head=self.head_dim, d_ff=self.d_ff,
                    vocab_size=self.vocab, tie_embeddings=self.tied,
                    rope_theta=self.rope_theta, norm_eps=self.norm_eps)


def seed_words(seed: int) -> Tuple[int, int]:
    """A run seed (any whole number) as two unsigned 32-bit words."""
    s = int(seed) % (1 << 64)
    return s & 0xFFFFFFFF, s >> 32
