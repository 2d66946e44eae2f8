"""A run whose timed path is broken underneath comes out not correct: the
harness's look for a chip is skipped (tiny.cpu_chip) and everything else
runs as on the chip, with one fault planted in the program's decode
cell for each kind of fault a serving cell can have."""
import pytest

from bench import run
from bench.tests import tiny


def _altered_token(orig):
    """The sampled tokens come back shifted by one id, where the decode
    cell produces them."""
    def cell(self, params, tokens, cache, key, active, *a, num_steps,
             **kw):
        toks, cache, key = orig(self, params, tokens, cache, key, active,
                                *a, num_steps=num_steps, **kw)
        return (toks + 1) % self.cfg.vocab_size, cache, key
    return cell


def _state_unchanged(orig):
    """The decode cell returns the page pools it was given: the step's
    key/value rows are never written."""
    def cell(self, params, tokens, cache, key, active, *a, num_steps,
             **kw):
        toks, new, key = orig(self, params, tokens, cache, key, active,
                              *a, num_steps=num_steps, **kw)
        return toks, dict(new, k=cache["k"], v=cache["v"]), key
    return cell


@pytest.mark.parametrize("cell", [tiny.CELL, tiny.BATCH_CELL])
@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged])
def test_fault_is_not_correct(fault, cell, tmp_path, monkeypatch):
    from repro.models.model import Model
    monkeypatch.setattr(Model, "decode_many_paged",
                        fault(Model.decode_many_paged))
    root = tiny.make_root(tmp_path)
    out = run.run(tiny.args(seed=2**31 + 11, seconds=3.0, cell=cell),
                  root=root, chip=tiny.cpu_chip)
    gap = out["checks"]["logit_gap_max"]
    assert out["correct"] is False
    assert gap["value"] > 3 * gap["limit"]
