"""Run one benchmark cell on the chip this process is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the program's ``PagedEngine`` for the cell's configuration
(bench/configs/, named in BENCHMARK.json) with weights made from the seed,
warms up every program the window runs and drives the cell's traffic
(bench/traffic/<mix>.json) through ``submit``/``step`` for the mix's
``prime_s`` (set-up, so the window opens on an engine in steady state),
then for ``--seconds`` (the window), then checks the served tokens
against the configuration's plain float32 reference (bench/correctness.py;
the module its file names, bench/reference.py by default).  With
``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` a sub-window is traced with the JAX profiler and the result
holds the per-layer metrics (bench/metrics/<name>.py), the device's busy
and window seconds and a breakdown.  The readers get a ``bench/record.py``
``Record``: the window, the engine's counters over it and over its traced
part, and, after the window has closed, the trace reduced per lane
(bench/trace.py) and per named scope and kernel (bench/spans.py, through
the lanes' compiled HLO, ``PagedEngine.cell_hlo()``).

The last line of standard output is one JSON object.  Without a TPU, with
fewer chips than the cell asks for, or on a device kind missing from
bench/peaks.py, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TRACE_LAST = 16.0       # seconds at the window's end that --trace 1 traces


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chip(chips: int):
    """(devices, peaks) of this process's TPU; exits non-zero otherwise."""
    import jax
    from bench.peaks import UnknownDevice, for_kind
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX's default platform is "
                         f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"the cell asks for {chips} chips; JAX finds "
                         f"{len(devices)}")
    try:
        peaks = for_kind(devices[0].device_kind)
    except UnknownDevice as e:
        raise SystemExit(str(e))
    return devices[:chips], peaks


class CompileCounter:
    """Counts programs lowered (traced to MLIR) and compiled by the
    backend while it is open and armed."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.counts = dict.fromkeys(self.EVENTS, 0)
        self.on = False

    def __call__(self, event, duration, **kw):
        if self.on and event in self.counts:
            self.counts[event] += 1
            print(f"compile in window: {kw.get('fun_name', event)}",
                  file=sys.stderr)

    def arm(self):
        """Start counting (at the window's opening)."""
        self.on = True

    def __enter__(self):
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        self.on = False
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self)

    @property
    def lowered(self) -> int:
        return self.counts[self.EVENTS[0]]

    @property
    def compiled(self) -> int:
        return self.counts[self.EVENTS[1]]


@contextlib.contextmanager
def profiled(log_dir: str, name: str):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        jax.profiler.stop_trace()


def load_limits(cell: str, root: Path = ROOT) -> dict:
    return json.loads((root / "bench" / "limits" / f"{cell}.json")
                      .read_text())


def check(reference, weights, sz, done, seed: int, limits: dict,
          gaps_of=None):
    """(correct, checks): the served tokens of a seeded sample of the
    requests that served tokens in the window (``correctness.at_close``)
    against the configuration's plain reference (``spec.load_reference``).
    ``gaps_of`` puts another source of gaps in the program's place
    (bench/control.py: the fp8 control)."""
    import numpy as np
    from bench import correctness
    gaps_of = gaps_of or correctness.served_gaps
    items = [done[i] for i in correctness.sample(done, seed)]
    gaps = gaps_of(reference, weights, sz, items)
    worst = float(gaps.max()) if gaps.size else float("nan")
    lim = limits["logit_gap_max"]["limit"]
    print(f"served tokens checked: {gaps.size} in {len(items)} of "
          f"{len(done)} requests that served tokens", file=sys.stderr)
    return (bool(np.isfinite(worst) and worst <= lim),
            {"logit_gap_max": {"value": worst, "limit": lim}})


def run(args, root: Path = ROOT, chip=require_chip) -> dict:
    from bench import spec
    bench = spec.load_benchmark(root)
    cell = spec.find_cell(bench, args.workload)
    conf = spec.load_config(bench, cell, root)
    reference = spec.load_reference(conf, root)
    mix = spec.load_mix(cell["traffic"], root)
    limits = load_limits(cell["name"], root)
    devices, peaks = chip(cell["chips"])

    from bench import correctness, generator, record, serve, spans, trace
    engine, weights, sz = serve.build(conf, args.seed)
    serve.warm(engine, sz.vocab)
    sessions = generator.schedule(mix, args.seed, args.seconds, sz.vocab)
    loop = serve.Driver(engine)
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    with CompileCounter() as cc:
        window = loop.run(
            sessions, args.seconds, prime_s=mix.get("prime_s", 0.0),
            trace=(lambda name: profiled(log_dir, name)) if args.trace
            else None,
            trace_at=args.seconds - min(TRACE_LAST, 0.5 * args.seconds),
            at_open=cc.arm)
    setup_s = window.t0 - T_START
    print(f"compiles in window: lowered={cc.lowered} "
          f"compiled={cc.compiled}", file=sys.stderr, flush=True)
    mem_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)
    finished = sum(1 for r in window.requests if r.ok)
    done = correctness.at_close(engine, window.primed + window.requests)
    failed = sum(1 for r in window.requests if r.ok is False)
    hlo = engine.cell_hlo() if args.trace else None
    del engine, loop
    gc.collect()

    red = sp = None
    if args.trace:
        red = trace.reduce(trace.load(log_dir),
                           other_kernels=conf.get("trace_kernels", ()))
        sp = spans.reduce(spans.load(log_dir, hlo,
                                     conf.get("trace_scopes", ())),
                          conf.get("trace_kernels", ()))
        shutil.rmtree(log_dir, ignore_errors=True)
    rec = record.Record(sz, peaks, window, setup_s, red, sp)
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in spec.cell_metrics(bench, cell["name"], kind):
        v = spec.metric_reader(m["name"], root)(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    correct, checks = check(reference, weights, sz, done, args.seed,
                            limits)
    admitted = sum(r.admit_t is not None for r in window.requests)
    print(f"requests due {len(window.requests)}, admitted {admitted}, "
          f"finished {finished}, failed {failed}, ticks "
          f"{len(window.ticks)}", file=sys.stderr)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem_peak}
    # requests the window worked on: those due in it, and those due while
    # priming that it was still serving (a closed loop's running clients)
    carried = sum(1 for r in window.primed
                  if r.ok is None or (r.last_t or 0.0) > window.t0)
    out = {"correct": correct, "attempted": len(window.requests) + carried,
           "failed": failed, "metrics": metrics, "device": device}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return out


def use_compile_cache() -> None:
    """Keep JAX's compile cache in the checkout, at a fixed path, so that
    a cell's later runs there find every program its first run compiled."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None) -> int:
    args = parse(argv)
    use_compile_cache()
    out = run(args)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
