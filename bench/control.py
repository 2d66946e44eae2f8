"""Readings that set a cell's ``logit_gap_max`` limit, taken on the chip at
the cell's own size: for each seed, one window of the cell's traffic
through the served path (primed as a run is), then over the same seeded
sample of requests that served tokens both the program's widest gap and
the fp8 control's (the configuration's reference in fp8 put in the
program's place).
Each goes through the check that decides a run's ``correct``
(``run.check`` against bench/limits/<cell>.json, when the cell has one):
the program has to come out correct, the control not.  The limit lies
between the program's largest reading and the control's smallest.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 51

Prints one JSON line per seed.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]


def readings(conf: dict, mix: dict, seed: int, seconds: float,
             limits: dict, reference, control: bool = True) -> dict:
    """One seed's window and the check of its program (and control)
    gaps against ``limits``, both against ``reference``, the
    configuration's module (``spec.load_reference``)."""
    from bench import correctness, generator, run, serve
    engine, weights, sz = serve.build(conf, seed)
    serve.warm(engine, sz.vocab)
    sessions = generator.schedule(mix, seed, seconds, sz.vocab)
    window = serve.Driver(engine).run(sessions, seconds,
                                        prime_s=mix.get("prime_s", 0.0))
    finished = sum(1 for r in window.requests if r.ok)
    done = correctness.at_close(engine, window.primed + window.requests)
    del engine
    gc.collect()
    items = [done[i] for i in correctness.sample(done, seed)]
    ok, checks = run.check(reference, weights, sz, done, seed, limits)
    out = {"seed": seed, "due": len(window.requests), "finished": finished,
           "served": len(done),
           "tokens": int(sum(len(s.tokens) for s in items)),
           "program": {"correct": ok, "checks": checks}}
    if control:
        ok, checks = run.check(reference, weights, sz, done, seed, limits,
                               gaps_of=correctness.control_gaps)
        out["control"] = {"correct": ok, "checks": checks}
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="how many of the seeds also read the control")
    args = ap.parse_args()
    from bench import run, spec
    run.use_compile_cache()
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    conf = spec.load_config(bench, cell)
    reference = spec.load_reference(conf)
    mix = spec.load_mix(cell["traffic"])
    try:
        limits = run.load_limits(cell["name"])
    except FileNotFoundError:           # a new cell: readings only
        limits = {"logit_gap_max": {"limit": float("inf")}}
    run.require_chip(cell["chips"])
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        r = readings(conf, mix, seed, args.seconds, limits, reference,
                     control=i < args.control_seeds)
        r["wall_s"] = time.perf_counter() - t
        print(json.dumps(r), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
