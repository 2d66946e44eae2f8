"""bench/costs.py against counts made by hand at a tiny shape: 2 layers,
d_model 8, 2 heads over 1 KV head of width 4, d_ff 16, vocab 10."""
import dataclasses
import json

import pytest

from bench import costs
from bench.spec import Sizes, SpecError

SZ = Sizes(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4, d_ff=16,
           vocab=10, tied=True, rope_theta=1e4, norm_eps=1e-5)


def test_prefill_attention():
    # slot 0: 3 resident, 2 granted -> rows attend 4 and 5 keys (9);
    # slot 1 idle.  ops 4*H*hd*9 = 288 per layer; bytes: K and V rows
    # 2*(3+2)*KV*hd*2 = 80, q and out 2*2*H*hd*2 = 64 -> 144 per layer
    assert costs.prefill_attention(SZ, [3, 0], [2, 0]) == (576.0, 288.0)


def test_decode_attention():
    # slot 0: 3 resident, 2 steps -> 4 + 5 keys; slot 1 inactive.
    # ops 4*2*4*9 = 288, bytes 2*9*1*4*2 + 2*2*2*4*2 = 208, per layer
    assert costs.decode_attention(SZ, [3, 5], [2, 0]) == (576.0, 416.0)


def test_model_flops():
    # per token per layer: q,o 2*8*8 each, k,v 2*8*4 each, FFN 3*2*8*16
    assert costs.linear_flops_per_token(SZ) == 2 * (128 + 64 + 384) * 2
    # 2 rows x 2304 + attention 576 + one unembedding 2*8*10
    assert costs.prefill_model_flops(SZ, [3, 0], [2, 0]) == 5344.0
    # 2 steps x (2304 + 160) + attention 576
    assert costs.decode_model_flops(SZ, [3, 5], [2, 0]) == 5504.0


@pytest.mark.parametrize("fn", [costs.prefill_attention,
                                costs.decode_attention])
def test_idle_slots_cost_nothing(fn):
    assert fn(SZ, [7, 9], [0, 0]) == (0.0, 0.0)


def _published(**keys):
    return dataclasses.replace(SZ, published=json.dumps(keys))


@pytest.mark.parametrize("fn", [costs.linear_flops_per_token,
                                lambda sz: costs.prefill_model_flops(
                                    sz, [3, 0], [2, 0]),
                                lambda sz: costs.decode_model_flops(
                                    sz, [3, 5], [2, 0])])
def test_routed_experts_are_not_counted_as_a_dense_ffn(fn):
    with pytest.raises(SpecError, match="routed-expert"):
        fn(_published(num_experts=4, num_experts_per_tok=2))


@pytest.mark.parametrize("fn", [costs.prefill_attention,
                                costs.decode_attention])
def test_attention_past_the_window_is_not_counted_as_full(fn):
    # slot 0 sees 3 + 2 = 5 keys at most: a window of 5 is full causal,
    # a window of 4 cuts its last query
    assert fn(_published(sliding_window=5), [3, 0], [2, 0]) == \
        fn(SZ, [3, 0], [2, 0])
    with pytest.raises(SpecError, match="sliding_window 4"):
        fn(_published(sliding_window=4), [3, 0], [2, 0])
