"""``chip_smoke.py`` on the CPU: it refuses to report success without a
TPU, and its serving and kernel-check phases run end to end at a reduced
width (Pallas kernels in interpret mode)."""
import dataclasses
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_fails_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(SCRIPT)], env=env,
                          cwd=SCRIPT.parent, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_chip_smoke_phases_at_reduced_width(smoke):
    cfg = dataclasses.replace(get(smoke.ARCH).reduced(),
                              attention_impl="pallas")
    stats = smoke.serve_requests(cfg, slots=2, page=8, sys_prompt=16,
                                 tails=(3, 12), n_requests=3, new_tokens=4,
                                 prefill_chunk=16, max_seq=48)
    assert stats["requests"] == 3 and stats["tokens"] == 3 * 4
    assert stats["shared_tokens"] > 0        # the system prompt is shared
    report = smoke.check_kernels(cfg, slots=2, page=8, max_blocks=4,
                                 chunks=(8, 16))
    assert [name for name, _, _ in report] == [
        "paged decode bf16 pages_per_step=derived",
        "paged decode bf16 pages_per_step=4",
        "paged decode int8 pages_per_step=derived",
        "paged decode int8 pages_per_step=4",
        "paged decode bf16 qwen2-0.5b widths",
        "paged decode int8 qwen2-0.5b widths",
        "paged decode bf16 zamba2-7b widths",
        "paged decode int8 zamba2-7b widths",
        "ragged prefill bf16 T=8", "ragged prefill bf16 T=16"]
    assert all(err <= smoke.KERNEL_TOL for _, err, _ in report)
