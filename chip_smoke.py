"""Bring-up smoke test on one TPU chip.

Serves phi4-mini-3.8b at its full published widths (32 layers, d_model
3072, 24 heads / 8 KV heads, head dim 128, vocab 200064) with seeded
random weights through the same engine construction as
``python -m repro.launch.serve`` (``build_engine``), with the Pallas paged
kernels on the served path (``attention_impl="pallas"``).  Then it checks
each paged kernel on the chip against its jnp oracle at the served shapes,
and the decode kernel also at two narrower head widths.

    python3 chip_smoke.py

Exits non-zero, printing no result line, unless JAX's default device is a
TPU.  Every failure propagates.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Compile and wall seconds are printed on earlier lines for information;
they are not benchmark numbers.
"""
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "phi4-mini-3.8b"
SLOTS = 4
PAGE = 16
SYS_PROMPT = 256            # page-aligned shared system prompt
TAILS = (44, 345)           # per-request tail: prompts of 300..600 tokens
N_REQUESTS = 8
NEW_TOKENS = 32
PREFILL_CHUNK = 256         # prompt tokens per prefill-lane step
MAX_SEQ = 640               # longest prompt + NEW_TOKENS, page-rounded
KERNEL_CHUNKS = (64, 256)   # ragged prefill chunks checked on the chip
# decode also checked at these configs' head widths (2 of their layers):
# head dims of 64 and 112, which fill part of a 128-lane row
NARROW_ARCHS = ("qwen2-0.5b", "zamba2-7b")
# bf16 outputs of two f32-accumulating softmax paths: a few bf16 ulps at
# unit magnitude (one ulp at 1.0 is 2**-7)
KERNEL_TOL = 2e-2
SEED = 0                    # prompts and kernel-check data (weights: 0)


def require_tpu():
    devices = jax.devices()
    print(f"devices: {devices}", flush=True)
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU; JAX's default "
                         f"platform is {devices[0].platform!r}")
    return devices


def serve_requests(cfg, *, slots, page, sys_prompt, tails, n_requests,
                   new_tokens, prefill_chunk, max_seq):
    """Build the engine, compile its cells on one warm-up request, then
    serve ``n_requests`` prompts (a shared system prompt plus a random
    tail each) to completion.  Raises unless every request finishes with
    ``new_tokens`` in-vocabulary tokens.  Returns timings and counts."""
    from repro.launch.serve import build_engine
    from repro.serve.engine import RequestStatus, ServeConfig

    scfg = ServeConfig(max_batch=slots, max_seq=max_seq,
                       max_new_tokens=new_tokens, page_size=page,
                       prefill_chunk_tokens=prefill_chunk)
    t0 = time.perf_counter()
    engine = build_engine(cfg, scfg)
    jax.block_until_ready(engine.params)
    build_s = time.perf_counter() - t0

    rng = np.random.RandomState(SEED)
    V = cfg.vocab_size
    shared = rng.randint(0, V, size=sys_prompt).astype(np.int32)
    prompts = [np.concatenate([shared, rng.randint(
        0, V, size=rng.randint(*tails)).astype(np.int32)])
        for _ in range(n_requests)]

    # the first request pays for compiling the prefill-lane and decode
    # cells; its prompt shares nothing with the measured requests
    t0 = time.perf_counter()
    warm = engine.submit(rng.randint(0, V, size=page + 1).astype(np.int32))
    engine.run()
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    ticks0, shared0 = engine.steps_run, engine.shared_tokens
    rids = [engine.submit(p) for p in prompts]
    results = engine.run()
    wall_s = time.perf_counter() - t0

    for rid in [warm] + rids:
        status = engine.status[rid]
        if status != RequestStatus.FINISHED:
            raise RuntimeError(f"request {rid} ended {status.value}: "
                               f"{engine.reject_reason.get(rid, '')}")
        out = np.asarray(results[rid])
        if len(out) != new_tokens or not ((0 <= out) & (out < V)).all():
            raise RuntimeError(f"request {rid}: {len(out)} tokens, "
                               f"range [{out.min()}, {out.max()}] "
                               f"outside vocab {V}")
    return {"requests": len(rids), "tokens": sum(len(results[r])
                                                 for r in rids),
            "prompt_tokens": sum(len(p) for p in prompts),
            "shared_tokens": engine.shared_tokens - shared0,
            "ticks": engine.steps_run - ticks0,
            "build_s": build_s, "compile_s": compile_s, "wall_s": wall_s}


def _paged_inputs(key, cfg, *, slots, page, max_blocks, T, kv_dtype):
    """Random stacked head-major pools, queries, distinct non-null pages
    per slot and a random layer — the shapes the served cells pass."""
    from repro.models.kv_quant import quantize_rows
    L, H, KV, hd = cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    num_pages = slots * max_blocks + 1
    kq, kk, kv_, kt, kl = jax.random.split(key, 5)
    q = jax.random.normal(kq, (slots, T, H, hd), jnp.float32)
    pools = [jax.random.normal(k, (L, num_pages, KV, page, hd), jnp.float32)
             for k in (kk, kv_)]
    if kv_dtype == "int8":
        (k_pool, k_scale), (v_pool, v_scale) = map(quantize_rows, pools)
    else:
        k_pool, v_pool = (p.astype(jnp.bfloat16) for p in pools)
        k_scale = v_scale = None
    table = jax.random.permutation(kt, jnp.arange(1, num_pages,
                                                  dtype=jnp.int32))
    layer = jax.random.randint(kl, (), 0, L, jnp.int32)
    return (q.astype(jnp.bfloat16), k_pool, v_pool,
            table.reshape(slots, max_blocks), layer, k_scale, v_scale)


def check_kernels(cfg, *, slots, page, max_blocks, chunks,
                  narrow=NARROW_ARCHS):
    """Each paged Pallas kernel against its jnp gather oracle at the
    served shapes: decode over bf16 and int8 pools at the page block the
    kernel derives from the pool and at 4 pages a block, decode at the
    head widths of each config in ``narrow`` (2 layers, derived block),
    and ragged prefill at each chunk in ``chunks``.  Returns
    [(name, max_abs_err, max_abs_ref)]; raises past ``KERNEL_TOL``."""
    from repro import configs
    from repro.kernels.decode_attention import ops, ref
    span = max_blocks * page
    rng = np.random.RandomState(SEED)
    key = jax.random.key(SEED)
    cases = []
    decodes = [(cfg, kv_dtype, pps, f"pages_per_step={pps or 'derived'}")
               for kv_dtype in ("bf16", "int8") for pps in (None, 4)]
    decodes += [(dataclasses.replace(configs.get(arch), n_layers=2),
                 kv_dtype, None, f"{arch} widths")
                for arch in narrow for kv_dtype in ("bf16", "int8")]
    for dcfg, kv_dtype, pps, label in decodes:
        key, sub = jax.random.split(key)
        q, kp, vp, tbl, layer, ks, vs = _paged_inputs(
            sub, dcfg, slots=slots, page=page, max_blocks=max_blocks,
            T=1, kv_dtype=kv_dtype)
        lens = jnp.asarray(rng.randint(1, span + 1, size=slots), jnp.int32)
        kernel = jax.jit(functools.partial(ops.paged_decode_attention,
                                           pages_per_step=pps))
        got = kernel(q, kp, vp, tbl, lens, layer, k_scale=ks, v_scale=vs)
        want = jax.jit(ref.paged_decode_attention_ref)(
            q, kp, vp, tbl, lens, layer, ks, vs)
        cases.append((f"paged decode {kv_dtype} {label}", got, want))
    for T in chunks:
        key, sub = jax.random.split(key)
        q, kp, vp, tbl, layer, _, _ = _paged_inputs(
            sub, cfg, slots=slots, page=page, max_blocks=max_blocks, T=T,
            kv_dtype="bf16")
        base = jnp.asarray(rng.randint(0, span - T + 1, size=slots),
                           jnp.int32)
        new = base + jnp.asarray(rng.randint(1, T + 1, size=slots),
                                 jnp.int32)
        got = jax.jit(ops.paged_prefill_attention)(q, kp, vp, tbl, base,
                                                   new, layer)
        want = jax.jit(ref.paged_prefill_attention_ref)(q, kp, vp, tbl,
                                                        base, new, layer)
        cases.append((f"ragged prefill bf16 T={T}", got, want))

    report = []
    for name, got, want in cases:
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = float(np.abs(got - want).max())
        scale = float(np.abs(want).max())
        print(f"kernel check: {name}: max_abs_err={err!r} "
              f"tol={KERNEL_TOL!r} (max |oracle| {scale!r})", flush=True)
        if not np.isfinite(got).all() or not err <= KERNEL_TOL:
            raise RuntimeError(f"{name}: kernel disagrees with its oracle "
                               f"(max abs err {err!r} > {KERNEL_TOL!r})")
        report.append((name, err, scale))
    return report


def main() -> int:
    devices = require_tpu()
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro import configs
    from repro.launch.serve import enable_compile_cache

    enable_compile_cache()
    cfg = dataclasses.replace(configs.get(ARCH), attention_impl="pallas")
    stats = serve_requests(cfg, slots=SLOTS, page=PAGE,
                           sys_prompt=SYS_PROMPT, tails=TAILS,
                           n_requests=N_REQUESTS, new_tokens=NEW_TOKENS,
                           prefill_chunk=PREFILL_CHUNK, max_seq=MAX_SEQ)
    print(f"served {stats['requests']} requests of {ARCH} "
          f"({stats['prompt_tokens']} prompt tokens, "
          f"{stats['shared_tokens']} served from shared pages, "
          f"{stats['tokens']} generated) in {stats['ticks']} ticks; "
          f"build_s={stats['build_s']!r} compile_s={stats['compile_s']!r} "
          f"wall_s={stats['wall_s']!r}", flush=True)
    check_kernels(cfg, slots=SLOTS, page=PAGE, max_blocks=MAX_SEQ // PAGE,
                  chunks=KERNEL_CHUNKS)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
