"""The one traffic generator: reads a mix file (``bench/traffic/<mix>.json``)
and turns it into a schedule of sessions from the run's seed.

Every seed gets the SAME schedule, so a seed never changes how much work
a run holds or when it comes: each declared distribution is sampled at
its stratified quantiles (the value at (i + 0.5) / n for i < n), and the
quantiles are dealt to sessions and turns in one fixed shuffled order
(arrival gaps, prefix popularity, turns, sizes).  The seed draws the
token ids, of the shared prefixes and of every turn.  A window holds a
few dozen requests at most, and a schedule rotated by the seed put a
different set of first sightings of the prefixes into each window,
which moved the tails more than the program did.

A mix file holds (all lengths in tokens):

  loop           "open": sessions arrive on a schedule, whatever the
                 server does (independent users); "closed": a fixed set
                 of clients, each sending its next request the moment its
                 previous one finishes (offline jobs, a queue never empty).
  sessions_per_s Poisson arrival rate of sessions (open loop).
  prime_s        seconds of the same schedule driven before the window
                 opens (set-up), so that the window opens on an engine in
                 steady state: slots busy, prefixes retained, sessions
                 part-way through.  Sessions due then have arrival_s < 0.
  turns          distribution of turns per session; a later turn is due
                 ``think_s`` after the previous turn's last token, and its
                 prompt is the session's whole history.
  think_s        distribution of think times between turns.
  prefixes       shared prompt prefixes (system prompts, repository
                 contexts): ``count`` of them, ``length`` distribution,
                 popularity Zipf with exponent ``zipf_s``.
  user           distribution of each turn's fresh prompt tokens.
  output         distribution of each turn's output budget (greedy, no
                 stop token, so a request emits exactly its budget).
  max_context    a session ends before a turn whose prompt plus output
                 would pass this.

A closed-loop mix holds ``clients``, ``requests_per_client``, ``prime_s``,
``user`` (each request's prompt) and ``output``.  Every client is due at
the start of priming.  So that completions are spread through the window
as in a queue that has run for a long time, each client's first request
comes part-way through: a stratified share of its budget is already
served, as tokens appended to its prompt, and the rest is its budget.

Distributions: {"dist": "uniform", "lo", "hi"} (integers, inclusive),
{"dist": "lognormal", "median", "sigma", "lo", "hi"} (rounded, clipped),
{"dist": "exponential", "mean"}, {"dist": "geometric", "mean", "lo",
"hi"} (on 1, 2, ...; clipped), {"dist": "fixed", "value"}.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Turn:
    user: np.ndarray           # fresh prompt tokens of this turn
    out_budget: int            # tokens the request is to emit
    think_s: float             # wait after the previous turn (0: first)


@dataclasses.dataclass
class Session:
    arrival_s: float           # due time of the first turn, from window open
    prefix_id: int
    prefix: np.ndarray
    turns: List[Turn]
    carry: bool = True         # a later turn's prompt holds the history


def _quantile(dist: dict, u: float) -> float:
    kind = dist["dist"]
    if kind == "fixed":
        return dist["value"]
    if kind == "uniform":
        lo, hi = dist["lo"], dist["hi"]
        return min(hi, lo + math.floor(u * (hi - lo + 1)))
    if kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
        return min(dist["hi"], max(dist["lo"], round(x)))
    if kind == "exponential":
        return -dist["mean"] * math.log1p(-u)
    if kind == "geometric":
        p = 1.0 / dist["mean"]
        k = max(1, math.ceil(math.log1p(-u) / math.log1p(-p)))
        return min(dist["hi"], max(dist["lo"], k))
    raise ValueError(f"unknown distribution {kind!r}")


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The n stratified quantiles of ``dist``, in increasing order."""
    return np.asarray([_quantile(dist, (i + 0.5) / n) for i in range(n)])


def zipf_ranks(count: int, s: float, n: int) -> np.ndarray:
    """n stratified draws of a rank in [0, count) with P(r) ~ 1/(r+1)^s."""
    p = 1.0 / np.arange(1, count + 1) ** s
    cdf = np.cumsum(p / p.sum())
    u = (np.arange(n) + 0.5) / n
    return np.minimum(np.searchsorted(cdf, u), count - 1)


def token_rng(seed: int) -> np.random.Generator:
    """The stream of token ids of one run seed (any whole number)."""
    return np.random.default_rng([int(seed) % (1 << 64), 1])


def schedule(mix: dict, seed: int, seconds: float, vocab: int
             ) -> List[Session]:
    """The sessions (open loop) or clients (closed loop) of a run."""
    if mix["loop"] == "closed":
        return closed_loop(mix, seed, vocab)
    return open_loop(mix, seed, seconds, vocab)


def closed_loop(mix: dict, seed: int, vocab: int) -> List[Session]:
    """One session per client, its requests as turns that carry no
    history and are due at once (``think_s`` 0)."""
    if mix["loop"] != "closed":
        raise ValueError(f"closed_loop needs a closed-loop mix, got "
                         f"{mix['loop']!r}")
    tok = token_rng(seed)
    base = np.random.default_rng(0)          # the same for every seed
    c, k = mix["clients"], mix["requests_per_client"]
    prompts = base.permutation(quantiles(mix["user"], c * k)).astype(int)
    outs = base.permutation(quantiles(mix["output"], c * k)).astype(int)
    served = base.permutation((np.arange(c) + 0.5) / c)
    empty = np.zeros((0,), np.int32)
    clients = []
    for i in range(c):
        turns = []
        for t in range(k):
            j = i * k + t
            n_prompt, out = int(prompts[j]), int(outs[j])
            if t == 0:                       # part-way through
                done = min(out - 1, int(served[i] * out))
                n_prompt, out = n_prompt + done, out - done
            turns.append(Turn(tok.integers(0, vocab, n_prompt,
                                           dtype=np.int32), out, 0.0))
        clients.append(Session(-float(mix["prime_s"]), -1, empty, turns,
                               carry=False))
    return clients


def open_loop(mix: dict, seed: int, seconds: float, vocab: int
              ) -> List[Session]:
    """The sessions that arrive in the ``prime_s`` before a window of
    ``seconds`` and in the window."""
    if mix["loop"] != "open":
        raise ValueError(f"open_loop needs an open-loop mix, got "
                         f"{mix['loop']!r}")
    tok = token_rng(seed)
    base = np.random.default_rng(0)          # the same for every seed
    rate = mix["sessions_per_s"]
    prime = float(mix.get("prime_s", 0.0))
    n = max(1, round(rate * (prime + seconds)))
    gaps = base.permutation(
        quantiles({"dist": "exponential", "mean": 1.0 / rate}, n))
    pre = mix["prefixes"]
    lengths = base.permutation(quantiles(pre["length"], pre["count"]))
    prefixes = [tok.integers(0, vocab, int(L), dtype=np.int32)
                for L in lengths]
    ranks = base.permutation(zipf_ranks(pre["count"], pre["zipf_s"], n))
    n_turns = base.permutation(quantiles(mix["turns"], n)).astype(int)
    m = int(n_turns.sum())
    users = base.permutation(quantiles(mix["user"], m)).astype(int)
    outs = base.permutation(quantiles(mix["output"], m)).astype(int)
    thinks = base.permutation(quantiles(mix["think_s"], max(1, m - n)))
    arrivals = np.cumsum(gaps) - prime
    first = np.concatenate([[0], np.cumsum(n_turns)[:-1]])
    sessions = []
    for i in range(n):
        history = int(lengths[ranks[i]])
        turns = []
        for t in range(n_turns[i]):
            j = first[i] + t
            history += users[j] + outs[j]
            if history > mix["max_context"]:
                break
            think = float(thinks[j - i - 1]) if t else 0.0
            turns.append(Turn(tok.integers(0, vocab, users[j], dtype=np.int32),
                              int(outs[j]), think))
        if turns:
            sessions.append(Session(float(arrivals[i]), int(ranks[i]),
                                    prefixes[ranks[i]], turns))
    return sessions
