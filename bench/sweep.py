"""Find a latency cell's knee: the highest rate the system sustains in
steady state, by a sweep on the chip.  Run once, when the cell is
defined; the cell's mix then fixes its rate at about four fifths of the
knee.

    python3 bench/sweep.py --workload <cell> --rates 0.3,0.5,0.7 --seconds 90

Builds and warms the cell's engine once, then for each rate drives the
cell's traffic at that rate, primed for the mix's ``prime_s`` as a run
is, through a window of ``--seconds`` (longer than a request holds its
slot, so a queue that builds shows), and prints one JSON line per rate:
requests due, finished and still waiting for a slot at the window's end,
the mean number waiting in the window's first and last thirds, and the
TTFT and TPOT percentiles.  Between rates every request is cancelled and
the retained prefixes are dropped.  A rate is sustained while the queue
does not grow: at most two requests waiting at the end, and the last
third's mean queue no longer than the first third's plus one.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]


def summary(w, rate: float, seconds: float) -> dict:
    """One rate's line: queue, completions and tails of a window."""
    from bench.record import percentile
    third = seconds / 3

    def mean_waiting(lo, hi):
        q = [t.waiting for t in w.ticks if lo <= t.t1 - w.t0 < hi]
        return sum(q) / len(q) if q else 0.0

    ttft = [(r.first_t or w.t1) - r.due for r in w.requests]
    tpot = [(r.last_t - r.first_t) / (r.n - 1)
            for r in w.requests if r.n >= 2]
    waiting_end = sum(r.admit_t is None for r in w.requests)
    first_q = mean_waiting(0, third)
    last_q = mean_waiting(2 * third, seconds + 1)
    return {
        "rate": rate, "due": len(w.requests),
        "finished": sum(bool(r.ok) for r in w.requests),
        "waiting_at_end": waiting_end,
        "waiting_mean_first_third": first_q,
        "waiting_mean_last_third": last_q,
        "sustained": waiting_end <= 2 and last_q <= first_q + 1,
        "ticks": len(w.ticks),
        "ttft_p50_ms": 1e3 * percentile(ttft, 50),
        "ttft_p90_ms": 1e3 * percentile(ttft, 90),
        "tpot_p90_ms": 1e3 * (percentile(tpot, 90) or 0),
        "tick_ms_mean": 1e3 * sum(t.t1 - t.t0 for t in w.ticks)
        / max(1, len(w.ticks))}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=90.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    from bench import generator, run, serve, spec
    run.use_compile_cache()
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    conf = spec.load_config(bench, cell)
    mix = spec.load_mix(cell["traffic"])
    run.require_chip(cell["chips"])
    t0 = time.perf_counter()
    engine, _, sz = serve.build(conf, args.seed)
    serve.warm(engine, sz.vocab)
    print(f"setup {time.perf_counter() - t0:.1f}s", file=sys.stderr)
    loop = serve.Driver(engine)
    for rate in map(float, args.rates.split(",")):
        m = dict(mix, sessions_per_s=rate)
        sessions = generator.open_loop(m, args.seed, args.seconds, sz.vocab)
        w = loop.run(sessions, args.seconds, prime_s=m.get("prime_s", 0.0))
        print(json.dumps(summary(w, rate, args.seconds)), flush=True)
        for r in w.requests + w.primed:
            engine.cancel(r.rid)
        engine.kv.flush_retained()
    return 0


if __name__ == "__main__":
    sys.exit(main())
