#!/usr/bin/env python3
"""Serve full-width zamba2-1.2b through the planned Pallas kernels on one TPU.

    python chip_smoke.py [--seed 0] [--out DIR]

One process drives the main path through its normal entry points:

  init     zamba2-1.2b at its registered config (38 layers, d_model 2048,
           vocab 32000), random params from ``--seed``;
  emit     a static ``tpu_v5e`` mapping artifact
           (`repro.launch.train.emit_static_mapping`, static activation
           scale) and a seeded request trace, both under ``--out``;
  serve    `repro.launch.serve.main` with ``--engine --mapping ART
           --require-full-coverage``: lower, bind, compile and serve 8
           requests in the paged engine (a coverage failure exits 2);
  check    first-step logits of 2 of those prompts, compiled kernels vs the
           pure-jnp oracle under the same quantization
           (``PlannedBackend(..., reference=True)``);
  layers   one 2048x8192 layer through ``split_precision`` (half int8, half
           bf16) and one through ``split_ternary``, each vs the oracle.

Without a TPU it exits 1 before doing any work: no CPU fallback, no
interpret mode.  Phase times, completed requests and peak device memory are
printed as information; the last line of stdout is one JSON object naming
the device.  `run_phases` holds the phases so that a test can rehearse them
at smoke size on the CPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "zamba2-1.2b"
# a static activation scale keeps each request's tokens independent of its
# batch neighbours (the engine's per-request reproducibility precondition)
ACT_LOG_SCALE = 2.0
# the kernel-vs-oracle tolerance of tests/test_runtime.py
RTOL = ATOL = 1e-4
# served requests whose first-step logits are checked against the oracle
CHECK_REQUESTS = 2


class SmokeFailure(RuntimeError):
    """A phase ran but its output is wrong."""


def _compare(tag, got, want, log):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        raise SmokeFailure(f"{tag}: non-finite outputs")
    diff = float(np.max(np.abs(got - want)))
    ok = bool(np.allclose(got, want, rtol=RTOL, atol=ATOL))
    log(f"{tag}: compiled vs reference max |diff| {diff:.3e} "
        f"(rtol={RTOL}, atol={ATOL}) agree={ok}")
    if not ok:
        raise SmokeFailure(f"{tag}: compiled kernels disagree with the "
                           f"reference (max |diff| {diff:.3e})")
    return diff


def _expect_kernel(tag, compiled, on_tpu, log):
    """On the chip, the compiled program must hold a Mosaic kernel."""
    if not on_tpu:
        return
    if "tpu_custom_call" not in compiled.as_text():
        raise SmokeFailure(f"{tag}: no tpu_custom_call in the compiled HLO")
    log(f"{tag}: compiled HLO holds tpu_custom_call")


def log_memory(when, log):
    """Log the device's memory counters; return the peak (None off-chip)."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"device memory {when}: bytes_in_use "
        f"{stats.get('bytes_in_use', 'not reported')} peak_bytes_in_use "
        f"{peak if peak is not None else 'not reported'}")
    return peak


def run_phases(arch: str, *, reduce: bool, seed: int, out_dir, requests=8,
               prompt_len=128, gen_len=32, max_batch=4,
               layer_shape=(2048, 8192), layer_rows=128, log=print) -> dict:
    """Run every phase on the default backend and return a summary.

    ``arch`` / ``reduce`` name the config exactly as ``serve --arch
    [--reduce]`` resolves it.  Raises `SmokeFailure` on a wrong result;
    a coverage failure inside serving raises ``SystemExit(2)``."""
    import jax
    import jax.numpy as jnp

    from repro.configs import base as cfgbase
    from repro.kernels.ops import align_boundary
    from repro.launch import serve
    from repro.launch.train import emit_static_mapping
    from repro.models import transformer as T
    from repro.models.managed import matmul_backend
    from repro.runtime import (KERNEL_SPLIT, KERNEL_SPLIT_TERNARY, LayerPlan,
                               PlannedBackend, execute_layer, lower,
                               prepare_layer)
    from repro.serving import ShedResult, save_trace, synthetic_trace

    out_dir = Path(out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    on_tpu = jax.default_backend() == "tpu"
    times = {}
    cfgbase.load_all()
    cfg = cfgbase.get(arch)
    if reduce:
        cfg = cfgbase.reduce_for_smoke(cfg)
    log(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"vocab {cfg.vocab}, reduced={reduce}")

    # ---- init ------------------------------------------------------------
    t = time.perf_counter()
    params = jax.block_until_ready(T.init_lm(jax.random.PRNGKey(seed), cfg))
    times["init"] = time.perf_counter() - t
    leaves = jax.tree.leaves(params)
    log(f"params: {sum(x.size for x in leaves)} "
        f"({sum(x.nbytes for x in leaves)} bytes)")

    # ---- emit ------------------------------------------------------------
    t = time.perf_counter()
    art_path = out_dir / "mapping_tpu_v5e.json"
    art = emit_static_mapping(params, cfg, "tpu_v5e", art_path,
                              act_log_scale=ACT_LOG_SCALE)
    trace = synthetic_trace(requests, vocab=cfg.vocab,
                            min_prompt=max(2, prompt_len // 4),
                            max_prompt=prompt_len,
                            min_new=max(2, gen_len // 4), max_new=gen_len,
                            seed=seed)
    trace_path = save_trace(out_dir / "trace.jsonl", trace)
    times["emit"] = time.perf_counter() - t

    # ---- serve: lower/bind, compile and run inside the engine ------------
    t = time.perf_counter()
    argv = ["--arch", arch, "--engine", "--mapping", str(art_path),
            "--require-full-coverage", "--trace", str(trace_path),
            "--max-batch", str(max_batch), "--seed", str(seed)]
    if reduce:
        argv.append("--reduce")
    results, _ = serve.main(argv)
    times["serve"] = time.perf_counter() - t
    done = {r.rid: r for r in results if not isinstance(r, ShedResult)}
    for req in trace:
        r = done.get(req.rid)
        if (r is None or r.finish_reason != "max_new_tokens"
                or r.n_tokens != req.max_new_tokens
                or not all(0 <= tok < cfg.vocab for tok in r.tokens)):
            raise SmokeFailure(f"request {req.rid} did not complete: {r}")
    log(f"served: {len(done)}/{len(trace)} requests completed, "
        f"{sum(r.n_tokens for r in done.values())} tokens")
    gc.collect()  # drop the engine's executables before compiling more
    log_memory("after serve", log)

    # ---- check: compiled kernels vs the oracle on served prompts ---------
    t = time.perf_counter()
    plan = lower(art, params=params)
    kernel = PlannedBackend(plan, params)
    oracle = PlannedBackend(plan, params, reference=True)
    if kernel.unbound:
        raise SmokeFailure(f"unbound planned layers: {kernel.unbound}")
    times["lower_bind"] = time.perf_counter() - t
    served_cfg = serve.planned_kv_cfg(cfg, art)  # the KV cache serving used
    reqs = trace[:CHECK_REQUESTS]
    lengths = np.array([r.prompt_len for r in reqs], np.int32)
    tokens = np.zeros((len(reqs), int(lengths.max())), np.int32)
    for i, r in enumerate(reqs):
        tokens[i, :r.prompt_len] = r.prompt
    caches = T.init_cache(served_cfg, len(reqs), tokens.shape[1])

    def compile_prefill(backend):
        # the backend is read while tracing, so each one gets its own jit
        with matmul_backend(backend):
            return jax.jit(lambda p, tk, c, n: T.prefill(
                p, served_cfg, tk, c, lengths=n)[0]).lower(
                    params, tokens, caches, lengths).compile()

    t = time.perf_counter()
    kernel_prefill = compile_prefill(kernel)
    oracle_prefill = compile_prefill(oracle)
    times["compile"] = time.perf_counter() - t
    _expect_kernel("served-model prefill", kernel_prefill, on_tpu, log)
    log(f"quant_matmul blocks by layer and rows: {kernel.kernel_blocks()}")
    got = kernel_prefill(params, tokens, caches, lengths)
    want = oracle_prefill(params, tokens, caches, lengths)
    logits_diff = _compare(f"first-step logits of {len(reqs)} served prompts",
                           got, want, log)
    # the engine prefills in chunks, so its first token may take a near-tie
    # the other way; the gap below the max says how near
    served = [done[r.rid].tokens[0] for r in reqs]
    rows = np.asarray(got, np.float32)
    log(f"first tokens: served {served}, compiled prefill argmax "
        f"{rows.argmax(axis=-1).tolist()}; served token's logit rank "
        f"{[int((row > row[tk]).sum()) for row, tk in zip(rows, served)]}, "
        f"below the max by "
        f"{[float(row.max() - row[tk]) for row, tk in zip(rows, served)]}")
    del kernel, oracle, kernel_prefill, oracle_prefill
    gc.collect()
    log_memory("after check", log)

    # ---- layers: the fused two-domain kernels at real width --------------
    t = time.perf_counter()
    k, n = layer_shape
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(k, n)) * k ** -0.5, jnp.float32)
    b = jnp.asarray(rng.normal(size=(n,)) * 0.1, jnp.float32)
    x = jnp.asarray(rng.normal(size=(layer_rows, k)), jnp.float32)
    w_ls = float(np.log(np.max(np.abs(np.asarray(w)))))
    half = n // 2
    layer_diff = {}
    for kname, bits in ((KERNEL_SPLIT, [8, 16]),
                        (KERNEL_SPLIT_TERNARY, [8, 2])):
        lp = LayerPlan(name=kname, kernel=kname, c_in=k, c_out=n,
                       perm=np.arange(n), counts=[half, n - half],
                       boundaries=[half, n],
                       aligned_boundaries=[align_boundary(half, 128), n],
                       w_log_scales=[w_ls, w_ls],
                       act_log_scale=ACT_LOG_SCALE)
        prep = prepare_layer(lp, w, b, domain_bits=bits)
        exe = jax.jit(lambda xx, prep=prep: execute_layer(prep, xx)).lower(
            x).compile()
        _expect_kernel(f"{kname} {k}x{n}", exe, on_tpu, log)
        ref = jax.jit(lambda xx, prep=prep: execute_layer(
            prep, xx, reference=True))
        layer_diff[kname] = _compare(f"{kname} {k}x{n} layer", exe(x),
                                     ref(x), log)
    times["layers"] = time.perf_counter() - t

    log("phase seconds: " + " ".join(f"{p}={s:.3f}" for p, s in times.items()))
    peak = log_memory("at the end", log)
    return {"model": cfg.name, "n_layers": cfg.n_layers,
            "d_model": cfg.d_model, "completed": len(done),
            "requests": len(trace), "times": times,
            "logits_max_abs_diff": logits_diff,
            "layer_max_abs_diff": layer_diff, "peak_bytes_in_use": peak}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(ROOT / "chip_smoke_out"),
                    help="directory for the mapping artifact and the trace")
    args = ap.parse_args(argv)

    import jax
    if jax.default_backend() != "tpu":
        print(f"[chip_smoke] needs a TPU; JAX found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        sys.exit(1)
    # before the first compile; the refusal above compiles nothing
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    dev = jax.devices()[0]
    count = len(jax.devices())
    print(f"[chip_smoke] device_kind={dev.device_kind} count={count}",
          flush=True)
    print(f"[chip_smoke] compile cache: {cache}", flush=True)
    run_phases(ARCH, reduce=False, seed=args.seed, out_dir=args.out,
               log=lambda s: print(f"[chip_smoke] {s}", flush=True))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
