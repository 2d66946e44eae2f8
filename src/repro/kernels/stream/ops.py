"""Jitted public wrappers for the BabelStream-TPU suite.

``interpret=None`` auto-selects (``repro.kernels.auto_interpret``): real
Pallas lowering on TPU, interpret mode (Python execution of the kernel
body) on the CPU backend, an error on any other backend.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import auto_interpret
from repro.kernels.stream import stream


@partial(jax.jit, static_argnames=("interpret",))
def stream_copy(a, interpret=None):
    return stream.copy(a, interpret=auto_interpret(interpret))


@partial(jax.jit, static_argnames=("interpret",))
def stream_mul(c, interpret=None):
    return stream.mul(c, interpret=auto_interpret(interpret))


@partial(jax.jit, static_argnames=("interpret",))
def stream_add(a, b, interpret=None):
    return stream.add(a, b, interpret=auto_interpret(interpret))


@partial(jax.jit, static_argnames=("interpret",))
def stream_triad(b, c, interpret=None):
    return stream.triad(b, c, interpret=auto_interpret(interpret))


@partial(jax.jit, static_argnames=("interpret",))
def stream_dot(a, b, interpret=None):
    return stream.dot(a, b, interpret=auto_interpret(interpret))
