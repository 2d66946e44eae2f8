"""Jitted wrapper for the Pallas selective scan; backward falls back to the
jnp sequential scan's autodiff (same math)."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import auto_interpret
from repro.kernels.ssm_scan import scan as _scan
from repro.models.ssm import mamba1_scan


@partial(jax.jit, static_argnames=("block_d", "chunk", "interpret"))
def selective_scan(x, dt, A, Bc, Cc, block_d: int = 256, chunk: int = 256,
                   interpret=None):
    return _scan.selective_scan_fwd(
        x, dt, A, Bc, Cc, block_d=block_d, chunk=chunk,
        interpret=auto_interpret(interpret))
