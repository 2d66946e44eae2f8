"""The traffic generator: deterministic under a seed, inside its declared
bounds, and the same schedule for every seed (the seed draws token ids)."""
import json

import numpy as np
import pytest

from bench import generator
from bench.spec import ROOT

MIXES = ["code_ctx"]


def _mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json")
                      .read_text())


def _flat(sessions):
    return [(s.arrival_s, s.prefix_id, len(s.prefix),
             [(t.user.tolist(), t.out_budget, t.think_s) for t in s.turns])
            for s in sessions]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    mix = _mix(name)
    a = generator.open_loop(mix, 2**31 + 77, 30.0, 1000)
    b = generator.open_loop(mix, 2**31 + 77, 30.0, 1000)
    assert _flat(a) == _flat(b)
    c = generator.open_loop(mix, 5, 30.0, 1000)
    assert _flat(a) != _flat(c)


@pytest.mark.parametrize("name", MIXES)
def test_bounds(name):
    mix = _mix(name)
    V = 500
    sessions = generator.open_loop(mix, 3, 40.0, V)
    assert sessions
    lo_p, hi_p = mix["prefixes"]["length"]["lo"], \
        mix["prefixes"]["length"]["hi"]
    prime = mix.get("prime_s", 0.0)
    assert any(s.arrival_s >= 0 for s in sessions)
    assert any(s.arrival_s < 0 for s in sessions) == (prime > 0)
    for s in sessions:
        assert -prime < s.arrival_s < 40.0
        assert lo_p <= len(s.prefix) <= hi_p
        assert 0 <= s.prefix_id < mix["prefixes"]["count"]
        history = len(s.prefix)
        for i, t in enumerate(s.turns):
            u, o = mix["user"], mix["output"]
            assert u["lo"] <= len(t.user) <= u["hi"]
            assert o["lo"] <= t.out_budget <= o["hi"]
            assert (t.think_s == 0.0) == (i == 0) or t.think_s == 0.0
            assert ((0 <= t.user) & (t.user < V)).all()
            history += len(t.user) + t.out_budget
        assert history <= mix["max_context"]
        assert len(s.turns) <= mix["turns"].get("hi", 1)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_same_work(name):
    """The arrival gaps (so the last arrival) and the prefix popularity
    counts are the same for every seed, and so are the sizes wherever no
    context cap cuts a session."""
    mix = _mix(name)

    def work(seed):
        ss = generator.open_loop(mix, seed, 30.0, 1000)
        sizes = (sorted(len(t.user) for s in ss for t in s.turns),
                 sorted(t.out_budget for s in ss for t in s.turns))
        return (sorted(s.prefix_id for s in ss), ss[-1].arrival_s
                if len(ss) else 0.0, sizes)

    base = work(1)
    for seed in (2, 3, 2**33 + 1):
        got = work(seed)
        assert got[0] == base[0]
        assert max(s.arrival_s for s in generator.open_loop(
            mix, seed, 30.0, 1000)) == pytest.approx(
            max(s.arrival_s for s in generator.open_loop(mix, 1, 30.0,
                                                         1000)))
        if mix["turns"]["dist"] == "fixed":
            assert got[2] == base[2]


def test_quantiles_follow_the_distributions():
    u = generator.quantiles({"dist": "uniform", "lo": 10, "hi": 19}, 10)
    assert u.tolist() == list(range(10, 20))
    ln = generator.quantiles({"dist": "lognormal", "median": 100,
                              "sigma": 1.0, "lo": 1, "hi": 10**6}, 101)
    assert ln[50] == 100
    ex = generator.quantiles({"dist": "exponential", "mean": 2.0}, 4000)
    assert abs(ex.mean() - 2.0) < 0.02
    ge = generator.quantiles({"dist": "geometric", "mean": 2.0, "lo": 1,
                              "hi": 4}, 1000)
    assert set(ge.tolist()) == {1, 2, 3, 4}
    assert abs((ge == 1).mean() - 0.5) < 0.01
    z = generator.zipf_ranks(4, 1.0, 2500)
    p = 1 / np.arange(1, 5)
    assert np.allclose(np.bincount(z) / 2500, p / p.sum(), atol=1e-3)


def test_closed_loop_clients():
    """Every client is due at the start of priming; its first request
    comes part-way through (a stratified share of its budget served, as
    prompt), the later ones whole; sizes are the same for every seed and
    the token ids are not."""
    mix = _mix("long_decode")
    V = 500
    a = generator.schedule(mix, 2**31 + 9, 51.0, V)
    assert len(a) == mix["clients"]
    u, o = mix["user"], mix["output"]
    for c in a:
        assert c.arrival_s == -mix["prime_s"] and not c.carry
        assert len(c.turns) == mix["requests_per_client"]
        assert len(c.prefix) == 0
        first = c.turns[0]
        assert first.out_budget >= 1
        total = len(first.user) + first.out_budget
        assert u["lo"] + o["lo"] <= total <= u["hi"] + o["hi"]
        for t in c.turns[1:]:
            assert u["lo"] <= len(t.user) <= u["hi"]
            assert o["lo"] <= t.out_budget <= o["hi"]
            assert t.think_s == 0.0
        for t in c.turns:
            assert ((0 <= t.user) & (t.user < V)).all()
    b = generator.schedule(mix, 17, 51.0, V)
    assert [[(len(t.user), t.out_budget) for t in c.turns] for c in a] == \
        [[(len(t.user), t.out_budget) for t in c.turns] for c in b]
    assert a[0].turns[0].user.tolist() != b[0].turns[0].user.tolist()
    budgets = sorted(c.turns[0].out_budget for c in a)
    assert budgets[0] < budgets[-1] // 4      # completions spread out
