"""The plain reference against the program's own forward pass at the
reduced() size of both configurations, on the benchmark's weights: equal
to bf16 rounding, and the fp8 control visibly further off."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, weights
from bench.spec import Sizes


def _setup(arch, seed):
    from repro import configs
    from repro.models import get_model
    cfg = configs.get(arch).reduced()
    sz = Sizes(layers=cfg.n_layers, d_model=cfg.d_model, heads=cfg.n_heads,
               kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
               d_ff=cfg.d_ff, vocab=cfg.vocab_size,
               tied=cfg.tie_embeddings, rope_theta=cfg.rope_theta,
               norm_eps=cfg.norm_eps)
    cfg = dataclasses.replace(cfg, **sz.arch_overrides())
    model = get_model(cfg)
    return model, weights.make(model.abstract_params(), seed), sz


@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "granite-8b"])
def test_reference_matches_program_logits(arch):
    model, w, sz = _setup(arch, seed=2**31 + 3)
    rng = np.random.default_rng(0)
    worst = 0.0
    for S in (32, 64, 96):         # the program chunks prompts by 32
        tok = rng.integers(0, sz.vocab, S, dtype=np.int32)
        got, _ = jax.jit(model.prefill)(w, {"tokens": jnp.asarray(tok)[None]})
        want = reference.logits(w, sz, tok, np.asarray([S - 1]))[0]
        scale = np.abs(want).max()
        worst = max(worst, np.abs(np.asarray(got[0]) - want).max() / scale)
    # the program computes in bf16 (2^-8 relative): a few ulps at most
    assert worst < 0.05, worst


def test_fp8_control_is_further_off():
    model, w, sz = _setup("phi4-mini-3.8b", seed=4)
    tok = np.random.default_rng(1).integers(0, sz.vocab, 64, dtype=np.int32)
    rows = np.arange(64)
    ref = reference.logits(w, sz, tok, rows)
    low = reference.logits(w, sz, tok, rows, fp8=True)
    got, _ = jax.jit(model.prefill)(w, {"tokens": jnp.asarray(tok)[None]})
    bf16_err = np.abs(np.asarray(got[0]) - ref[-1]).max()
    fp8_err = np.abs(low - ref).max(axis=1)
    assert fp8_err.mean() > 2 * bf16_err
