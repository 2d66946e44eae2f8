"""What decides ``correct``: the served tokens against the plain reference.

Once the window has closed, a sample drawn from the seed of the requests
that served tokens in it, finished or still running at the close (always
with the one that served the most tokens), is run through the
configuration's float32 reference (``spec.load_reference``: the module
its file names, bench/reference.py by default) over prompt + served
tokens.
For every served token the gap is how far its reference logit lies below
the reference's best logit at that position: 0 where the program picked
the reference's argmax, small where rounding flipped a near-tie, large
where the served path computed something else.  The widest gap is the
number compared.

The control (bench/tests/test_control.py, bench/control.py) puts the
reference computed in fp8 in the program's place: at each position of the
same sequences it reads the gap of the token that fp8 puts first.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence

import numpy as np

from bench.spec import Sizes

MIN_TOKENS = 384           # served tokens the sample holds at least
MAX_REQUESTS = 6           # ... unless it reaches this many requests


@dataclasses.dataclass
class Served:
    prompt: np.ndarray
    tokens: np.ndarray     # what the engine emitted, in order


def at_close(engine, requests) -> List[Served]:
    """What each request of the run (those due while priming too, which
    the window kept serving) had served when the window closed.  Every
    request still queued or running is cancelled first, which keeps its
    partial output in ``engine.results``; one with no token is left out.
    Requests that outlive the window count with what they served by its
    close, so the sample holds the longest contexts the window ran."""
    for r in requests:
        engine.cancel(r.rid)
    out = [Served(r.prompt, np.asarray(engine.results[r.rid], np.int32))
           for r in requests]
    return [s for s in out if len(s.tokens)]


def sample(done: Sequence[Served], seed: int) -> List[int]:
    """Indices into ``done``: the request with the most served tokens,
    then others in an order drawn from the seed, until the sample holds
    ``MIN_TOKENS`` served tokens or ``MAX_REQUESTS`` requests."""
    if not done:
        return []
    longest = int(np.argmax([len(d.tokens) for d in done]))
    rest = np.random.default_rng([int(seed) % (1 << 64), 2]).permutation(
        len(done))
    picked, n = [longest], len(done[longest].tokens)
    for i in rest:
        if n >= MIN_TOKENS or len(picked) >= MAX_REQUESTS:
            break
        if i != longest:
            picked.append(int(i))
            n += len(done[i].tokens)
    return picked


def _positions(s: Served):
    seq = np.concatenate([s.prompt, s.tokens]).astype(np.int32)
    P = len(s.prompt)
    return seq, np.arange(P - 1, P - 1 + len(s.tokens))


def served_gaps(reference, weights, sz: Sizes,
                items: Sequence[Served]) -> np.ndarray:
    """Per served token: reference best logit minus the served token's.
    ``reference`` is the configuration's module (``spec.load_reference``)."""
    out = []
    for s in items:
        seq, rows = _positions(s)
        ref = reference.logits(weights, sz, seq, rows)
        out.append(ref.max(1) - ref[np.arange(len(rows)), s.tokens])
    return np.concatenate(out) if out else np.zeros((0,))


def control_gaps(reference, weights, sz: Sizes,
                 items: Sequence[Served]) -> np.ndarray:
    """Per position of the same sequences: reference best logit minus the
    reference logit of the token the fp8 reference puts first."""
    out = []
    for s in items:
        seq, rows = _positions(s)
        ref = reference.logits(weights, sz, seq, rows)
        low = reference.logits(weights, sz, seq, rows, fp8=True)
        out.append(ref.max(1) - ref[np.arange(len(rows)), low.argmax(1)])
    return np.concatenate(out) if out else np.zeros((0,))
