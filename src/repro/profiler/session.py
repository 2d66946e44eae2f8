"""Profiling session: turn one compiled XLA step into an instruction-roofline
record — the paper's per-kernel table row, generalized to a distributed step.

This is the integration point of the whole system: dry-run -> compiled
artifact -> {cost_analysis, memory_analysis, HLO census} -> three-term
roofline + TPU instruction profile (Eq. 2/3/4 analogues).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from repro.core.hardware import HardwareSpec, TPU_V5E
from repro.core.hlo_counters import census_from_compiled
from repro.core.report import census_summary
from repro.core.roofline import roofline_terms, to_row
from repro.core.tpu_model import profile_from_census


def _memory_dict(mem) -> Dict[str, float]:
    keys = ("generated_code_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "alias_size_in_bytes",
            "temp_size_in_bytes")
    out = {}
    for k in keys:
        out[k] = float(getattr(mem, k, 0) or 0)
    out["device_total_bytes"] = (out["argument_size_in_bytes"]
                                 + out["output_size_in_bytes"]
                                 + out["temp_size_in_bytes"]
                                 - out["alias_size_in_bytes"])
    return out


def profile_compiled(name: str, compiled, n_devices: int,
                     hw: HardwareSpec = TPU_V5E,
                     model_flops: Optional[float] = None) -> Dict[str, Any]:
    census = census_from_compiled(compiled)
    terms = roofline_terms(name, census, hw, n_devices,
                           model_flops_total=model_flops)
    tpu_prof = profile_from_census(name, census, hw,
                                   runtime_s=max(terms.modeled_time_s, 1e-12),
                                   runtime_is_modeled=True)
    try:
        cost = dict(compiled.cost_analysis())
    except Exception:                                 # pragma: no cover
        cost = {}
    try:
        mem = _memory_dict(compiled.memory_analysis())
    except Exception:                                 # pragma: no cover
        mem = {}
    return {
        "name": name,
        "n_devices": n_devices,
        "hw": hw.name,
        "memory": mem,
        "xla_cost_analysis": {k: float(v) for k, v in cost.items()
                              if isinstance(v, (int, float))
                              and not k.startswith("utilization")},
        "census": census_summary(census),
        "roofline": to_row(terms),
        "irm": tpu_prof.table_row(),
    }
