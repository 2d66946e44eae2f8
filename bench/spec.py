"""Where the benchmark's pieces live, found by the names in
``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, so a later change adds a cell
by adding files and entries, never by editing one:

  * ``BENCHMARK.json`` ``configs[].file``      — model sizes + serving settings
  * the module a config file names under ``reference`` (default
    ``bench/reference.py``)                     — its plain reference
  * a config file's ``trace_scopes`` and ``trace_kernels`` (lists of
    names, default none)                        — the named scopes and
    Pallas kernels its program adds, read from a ``--trace 1`` run beside
    the dense decoder's (bench/spans.py); a kernel named here is no
    lane's attention kernel (bench/trace.py), so it brings a roofline
    reader of its own
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


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ident(name: str) -> str:
    return name.replace(".", "_").replace("-", "_")


def metric_reader(name: str, root: Path = ROOT) -> Reader:
    """``read(record)`` from ``bench/metrics/<name>.py``.  A reader that
    finds nothing to read returns None."""
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"per-layer metric {name!r} has no reader {path}")
    return _load_module(path, "bench_metric_" + _ident(name)).read


def load_reference(conf: dict, root: Path = ROOT):
    """The plain reference of a configuration: the module its file names
    under ``reference`` (a path from the checkout root), or
    bench/reference.py, the dense GQA decoder's, where it names none.
    The module's interface is ``logits(weights, sizes, tokens, rows,
    fp8=False)`` (bench/reference.py says what each argument holds)."""
    rel = conf.get("reference")
    if rel is None:
        from bench import reference
        return reference
    path = root / rel
    if not path.is_file():
        raise SpecError(f"config {conf.get('name')!r} names reference "
                        f"{rel!r}, which has no file {path}")
    mod = _load_module(path, "bench_reference_" + _ident(path.stem))
    if not callable(getattr(mod, "logits", None)):
        raise SpecError(f"reference {rel!r} defines no logits()")
    return mod


# Keys of a config file that are the benchmark's own, not published sizes.
HARNESS_KEYS = frozenset({
    "name", "source", "paper", "registry_id", "program_settings",
    "reference", "reduced", "reduced_from", "deployment", "departures",
    "assumed", "max_context", "serving", "trace_scopes", "trace_kernels"})

# Sizes' dense fields and the published (Hugging Face) key each is read from.
DENSE_KEYS = {
    "layers": "num_hidden_layers", "d_model": "hidden_size",
    "heads": "num_attention_heads", "kv_heads": "num_key_value_heads",
    "head_dim": "head_dim", "d_ff": "intermediate_size",
    "vocab": "vocab_size", "tied": "tie_word_embeddings",
    "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps"}


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The published sizes a config file holds (Hugging Face key names).

    The fields are a dense GQA decoder's, which the dense reference and
    bench/costs.py read; one whose key the file lacks is None.  Every
    other published key of the file (``num_experts``, ``sliding_window``,
    ``layer_types``, ``rope_parameters``, ...) is kept in ``published``
    as canonical JSON, so that a Sizes stays hashable (the references jit
    on it as a static argument); ``get`` reads one back."""
    layers: Optional[int]
    d_model: Optional[int]
    heads: Optional[int]
    kv_heads: Optional[int]
    head_dim: Optional[int]
    d_ff: Optional[int]
    vocab: Optional[int]
    tied: Optional[bool]
    rope_theta: Optional[float]
    norm_eps: Optional[float]
    published: str = "{}"

    @classmethod
    def from_config(cls, conf: dict) -> "Sizes":
        dense = {f: conf.get(k) for f, k in DENSE_KEYS.items()}
        for f in ("rope_theta", "norm_eps"):
            if dense[f] is not None:
                dense[f] = float(dense[f])
        rest = {k: v for k, v in conf.items()
                if k not in HARNESS_KEYS and k not in DENSE_KEYS.values()}
        return cls(**dense, published=json.dumps(rest, sort_keys=True))

    def get(self, key: str, default=None):
        """A published key of the file that no field holds, as JSON reads
        it, or ``default`` where the file lacks it."""
        return json.loads(self.published).get(key, default)

    def arch_overrides(self) -> Dict[str, object]:
        """The dense sizes the file gives, under the program's
        ``ArchConfig`` field names."""
        named = dict(n_layers=self.layers, d_model=self.d_model,
                     n_heads=self.heads, n_kv_heads=self.kv_heads,
                     d_head=self.head_dim, d_ff=self.d_ff,
                     vocab_size=self.vocab, tie_embeddings=self.tied,
                     rope_theta=self.rope_theta, norm_eps=self.norm_eps)
        return {k: v for k, v in named.items() if v is not None}


def seed_words(seed: int) -> Tuple[int, int]:
    """A run seed (any whole number) as two unsigned 32-bit words."""
    s = int(seed) % (1 << 64)
    return s & 0xFFFFFFFF, s >> 32
