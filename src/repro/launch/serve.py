"""Serving driver: a thin client of the `repro.serving` continuous-batching
engine.

``serve_batch`` (same-length batch, fixed generation budget) and ``serve
--mapping`` submit their requests to an `repro.serving.Engine` — B slots,
one shared KV-cache pool, jitted ragged prefill + per-slot-masked decode.
``--engine`` exposes the engine directly: it replays a mixed-length request
trace (``--trace requests.jsonl``, or a seeded synthetic trace) with
continuous slot admission/retirement and reports PER-REQUEST latency — TTFT
p50/p95 and decode tok/s — alongside the per-kernel coverage histogram:

    PYTHONPATH=src python -m repro.launch.serve --arch zamba2-1.2b --reduce \
        --engine --requests 8 --mapping art.json --require-full-coverage

With ``--mapping`` the driver lowers the mapping artifact onto the model's
actual weights (`repro.runtime.lower`) and executes every projection matmul
the plan binds to through its per-layer planned kernel — split-precision /
quant-matmul / ternary, interpret mode on CPU — via the NAME-KEYED pluggable
matmul backend (`repro.runtime.PlannedBackend`).  Because plans resolve by
the layer's pytree path (a static string), prefill and decode run under
``jax.jit`` with the planned kernels executing INSIDE the trace, and
scan-stacked LM weights (``base@r`` plan names) bind too — the measured
latency/energy is the mapped latency/energy, not a silent fp fallback.  The
artifact's activation majority still decides the KV-cache dtype (an
activation-precision choice the per-layer weight kernels don't cover).
Artifacts that fail to lower or bind (shape mismatch / wrong model /
stacked repeat-count mismatch) fall back to the legacy global
majority-dtype path (`apply_mapping_artifact`);
``--require-full-coverage`` turns partial binding into a nonzero exit
instead.

MULTI-PLAN SERVING — a second mapping artifact of the SAME weights turns
the backend into a `repro.runtime.PlanSet` precision bank (prepared
buffers deduplicated wherever layers coincide across artifacts):

  * ``--speculate draft.json`` binds ``{"draft", "target"}`` variants
    (``--mapping`` is the target) and serves with SELF-SPECULATIVE
    decoding: ``--draft-k`` tokens drafted per round with the draft
    variant, verified in one target-variant chunk — token-identical to
    target-only greedy serving (``--check-spec-parity`` replays the trace
    target-only and asserts it).  Emit the pair with ``train
    --emit-mapping --mapping-bias aimc ...`` / ``--mapping-bias digital``
    and a static ``--mapping-act-scale``.
  * ``--slo-variant CLASS=alt.json`` (repeatable) binds one variant per
    SLO class and routes each request's class to its variant (synthetic
    traces are tagged round-robin with the route classes); ``summarize``
    then reports per-class TTFT/decode-rate.
  * ``--require-full-coverage`` checks EVERY variant of the bank and exits
    2 naming the first offending variant; the per-variant coverage diff
    prints layer NAMES, not counts.

ROBUSTNESS — the engine's deadline scheduling, overload handling and fault
containment are driven from the same CLI:

  * ``--policy deadline`` orders admission by priority/slack and preempts
    a running slot for a more urgent arrival (``--priorities`` /
    ``--deadlines-ms`` tag synthetic requests round-robin);
    ``--check-preempt-parity`` replays the trace FCFS-without-preemption
    and exits nonzero unless every completed request's tokens match.
  * ``--poisson RATE`` restamps arrivals as a seeded open-loop Poisson
    process at RATE requests/step; ``--max-queue-depth`` /
    ``--page-watermark`` / ``--request-timeout`` shed overload as
    structured `ShedResult`s instead of queueing forever.
  * ``--fault-spec`` injects seeded faults
    (``kind@step:slot[xN]`` / ``kind~rate``; kinds: nonfinite_logits,
    corrupt_page, stuck) that the engine detects, quarantines and
    requeues — the summary line reports detections/requeues/sheds.
  * ``--degrade-to CLASS --ttft-target-s S`` routes NEW requests to the
    CLASS variant of the ``--slo-variant`` bank while the sliding p95
    TTFT exceeds S, and back once it recovers.

A ``--trace`` path that is missing or malformed exits 2 with a message
naming the file (and line) instead of a traceback.

CNN artifacts serve through the same flag with the ``cnn:<config>`` arch
convention — the conv layers execute through the im2col'd planned kernels:

    PYTHONPATH=src python -m repro.launch.serve --arch cnn:resnet20_tiny \
        --requests 8 --mapping art.json --require-full-coverage

Example (CPU, reduced LM):
    PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --reduce \
        --requests 8 --prompt-len 32 --gen-len 16 [--mapping art.json]
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import base as cfgbase
from repro.models import transformer as T
from repro.models.managed import matmul_backend


def apply_mapping_artifact(cfg, artifact):
    """FALLBACK consumer: pick GLOBAL serving dtypes from a
    `repro.api.MappingArtifact` majority vote.

    Only ``searchable: true`` layers vote (pinned layers never had a choice;
    counting them would let a wide pinned stem outvote the search).  The
    majority precision domain decides the weight stream: a <=8-bit majority
    serves int8 projections; an int8 activation majority additionally
    quantizes the KV cache.  Returns the updated cfg and the majority domain
    dict.

    This is the documented fallback when no `ExecutionPlan` can be lowered —
    the first-class path is per-layer planned execution via
    `plan_mapping_execution`.
    """
    dom = _majority_domain(artifact)
    updates = {}
    if dom["weight_bits"] <= 8:
        updates["serve_weight_dtype"] = "int8"
    if dom.get("act_bits", 16) <= 8:
        updates["kv_cache_dtype"] = "int8"
    if updates:
        cfg = dataclasses.replace(cfg, **updates)
    return cfg, dom


def _majority_domain(artifact):
    fractions = artifact.domain_channel_fractions(searchable_only=True)
    return artifact.domains[int(np.argmax(fractions))]


def planned_kv_cfg(cfg, artifact):
    """The cfg the planned path serves with: the weight kernels don't cover
    the KV cache, so it is int8 when the artifact's majority domain
    quantizes activations, as on the fallback path."""
    if _majority_domain(artifact).get("act_bits", 16) <= 8:
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    return cfg


def plan_mapping_execution(params, artifact, interpret=None):
    """Lower ``artifact`` against ``params`` and bind a planned backend.

    Returns (plan, backend).  Raises `repro.runtime.LoweringError` when the
    artifact does not lower onto the model, and `repro.runtime
    .ExecutionError` when the lowered plan cannot bind (e.g. a stacked
    repeat-count mismatch); callers catch both and fall back to
    `apply_mapping_artifact`.
    """
    from repro.runtime import PlannedBackend, lower
    plan = lower(artifact, params=params)
    backend = PlannedBackend(plan, params, interpret=interpret)
    return plan, backend


def build_planset(params, artifacts, default, interpret=None):
    """Lower several mapping artifacts of the SAME weights and bind them as
    one `repro.runtime.PlanSet` precision bank.

    ``artifacts``: {variant_name: MappingArtifact}.  Returns
    (plans, planset) with ``plans`` the per-variant `ExecutionPlan`s.
    Raises `LoweringError` / `ExecutionError` — multi-plan serving has no
    majority-dtype fallback (a bank that cannot bind is an error, not a
    degraded mode)."""
    from repro.runtime import PlanSet, lower
    plans = {v: lower(art, params=params) for v, art in artifacts.items()}
    planset = PlanSet(plans, params, default=default, interpret=interpret)
    return plans, planset


def print_planset_report(tag, plans, planset):
    """Per-variant coverage + the dedup memory accounting of the bank."""
    for v in planset.variant_names:
        hist = " ".join(f"{k}:{n}" for k, n in
                        sorted(plans[v].kernel_histogram().items()))
        bp = planset.variant(v)
        print(f"[{tag}] variant {v!r}: {hist}; {len(bp.bound)}/"
              f"{len(bp.plan.layers)} planned layers bound to weights, "
              f"{len(bp.unbound)} unbound")
    rep = planset.memory_report()
    shared = rep["shared_layers"]
    print(f"[{tag}] planset memory: prepared_bytes={rep['prepared_bytes']} "
          f"sum_variant_bytes={rep['sum_variant_bytes']} "
          f"dedup_saved_bytes={rep['dedup_saved_bytes']} "
          f"shared_layers={len(shared)}")
    diff = planset.coverage_diff()
    for v, missing in sorted(diff.items()):
        print(f"[{tag}] coverage diff: variant {v!r} leaves unbound: "
              f"{missing}")


def print_plan_coverage(tag, plan, backend):
    """Per-layer kernel/coverage report + the greppable summary line.

    Leads with the per-kernel layer histogram and every fp-fallback reason
    (layer names included) so capability fallbacks are visible at a glance
    — not only via ``--require-full-coverage``."""
    hist = " ".join(f"{k}:{v}" for k, v in
                    sorted(plan.kernel_histogram().items()))
    for line in plan.histogram_lines():
        print(f"[{tag}] {line}")
    print(f"[{tag}] per-layer planned execution ({hist}; "
          f"{backend.coverage()})")
    for lp in plan.layers:
        mark = "*" if lp.name in backend.bound else " "
        note = f"  ({lp.note})" if lp.note else ""
        print(f"[{tag}]  {mark} {lp.name}: {lp.kernel} "
              f"counts={lp.counts}{note}")


def check_coverage(tag, backend, require_full: bool):
    """Enforce ``--require-full-coverage``: exit 2 when any planned layer is
    unbound or declined at trace time.  Multi-variant `PlanSet` banks are
    checked variant by variant — the exit names the offending variant and
    its unplanned layer NAMES."""
    declines = backend.runtime_declines or {}
    for name, reason in sorted(declines.items()):
        print(f"[{tag}] declined at trace time: {name}: {reason}")
    if not require_full:
        return
    variants = list(getattr(backend, "variant_names", ()) or ())
    if len(variants) > 1:
        diff = backend.coverage_diff()
        for v in variants:
            problems = list(diff.get(v, [])) + \
                [k.split(":", 1)[1] for k in sorted(declines)
                 if k.startswith(f"{v}:")]
            if problems:
                print(f"[{tag}] ERROR: --require-full-coverage but variant "
                      f"{v!r}: {len(problems)} planned layers did not "
                      f"execute as mapped: {problems}", file=sys.stderr)
                sys.exit(2)
        return
    problems = list(backend.unbound) + sorted(declines)
    if problems:
        print(f"[{tag}] ERROR: --require-full-coverage but "
              f"{len(problems)} planned layers did not execute as mapped: "
              f"{problems}", file=sys.stderr)
        sys.exit(2)


def serve_batch(cfg, params, prompts, gen_len: int, frontend=None,
                backend=None):
    """prompts: (B, P) int32. Returns generated (B, gen_len).

    MIGRATED: this is now a thin wrapper over the `repro.serving.Engine` —
    the B same-length prompts are submitted as B requests with a shared
    generation budget, admitted into B slots at once, and decoded to
    completion (token-identical to the old fixed-shape loop; the engine's
    per-slot machinery degenerates to it for a uniform batch).  Prefill and
    decode run under ``jax.jit`` with or without a matmul ``backend``; use
    the engine directly for mixed lengths / queueing / EOS / TTFT.
    """
    from repro.serving import Engine, Request
    B, P = prompts.shape
    prompts_np = np.asarray(prompts)
    frontend_np = None if frontend is None else np.asarray(frontend)
    reqs = [Request(rid=b, prompt=prompts_np[b], max_new_tokens=gen_len,
                    frontend=(frontend_np[b] if frontend_np is not None
                              else None))
            for b in range(B)]
    engine = Engine(cfg, params, max_batch=B, max_len=P + gen_len,
                    backend=backend, prefill_bucket=P)
    results = engine.run(reqs)
    gen = jnp.asarray(np.stack([r.tokens for r in results]))
    st = engine.stats
    return gen, {"prefill_s": st["prefill_s"], "decode_s": st["decode_s"],
                 "tok_per_s": B * (gen_len - 1) / max(st["decode_s"], 1e-9)}


def serve_engine(args, cfg, params, backend=None):
    """``--engine``: replay a mixed-length request trace through the
    continuous-batching engine and report per-request latency (TTFT,
    decode tok/s) + the run summary.  The trace comes from ``--trace``
    (JSONL, see `repro.serving.trace`) or a seeded synthetic trace sized by
    ``--requests/--prompt-len/--gen-len``.  With ``--speculate`` the run is
    self-speculative (and ``--check-spec-parity`` replays it target-only to
    assert token identity); with ``--slo-variant`` routes each request's
    SLO class to its plan variant."""
    from repro.serving import (Engine, FaultInjector, SamplingParams,
                               Scheduler, ShedResult, load_trace,
                               poisson_arrivals, summarize, synthetic_trace)
    speculate = ("draft", "target") if args.speculate else None
    # the --degrade-to class is bound in the bank but is NOT an SLO route:
    # requests reach it only while the engine is degraded, never by tag
    route_classes = [c for c in getattr(args, "slo_classes", [])
                     if c != args.degrade_to]
    slo_routes = ({cls: cls for cls in route_classes}
                  if route_classes else None)
    sampling = None
    if args.temperature is not None or args.top_p < 1.0:
        sampling = SamplingParams(
            temperature=(args.temperature if args.temperature is not None
                         else 1.0),
            top_p=args.top_p, seed=args.seed)
    if args.trace:
        try:
            trace = load_trace(args.trace, vocab=cfg.vocab)
        except FileNotFoundError:
            print(f"[serve] ERROR: trace file not found: {args.trace}",
                  file=sys.stderr)
            sys.exit(2)
        except (ValueError, OSError) as e:
            print(f"[serve] ERROR: bad trace: {e}", file=sys.stderr)
            sys.exit(2)
        print(f"[serve] trace {args.trace}: {len(trace)} requests")
    else:
        priorities = ([int(p) for p in args.priorities.split(",")]
                      if args.priorities else None)
        deadlines = ([None if d in ("", "none") else float(d)
                      for d in args.deadlines_ms.split(",")]
                     if args.deadlines_ms else None)
        trace = synthetic_trace(
            args.requests, vocab=cfg.vocab,
            min_prompt=max(2, args.prompt_len // 4),
            max_prompt=args.prompt_len,
            min_new=max(2, args.gen_len // 4), max_new=args.gen_len,
            seed=args.seed, shared_prefix=args.shared_prefix,
            slo_classes=(sorted(slo_routes) if slo_routes else None),
            priorities=priorities, deadlines_ms=deadlines)
        print(f"[serve] synthetic trace: {len(trace)} mixed-length requests "
              f"(prompts <= {args.prompt_len}, gen <= {args.gen_len}, "
              f"shared prefix {args.shared_prefix})")
    if args.poisson:
        trace = poisson_arrivals(trace, args.poisson, seed=args.seed)
        print(f"[serve] open-loop arrivals: Poisson at {args.poisson} "
              f"req/step (last arrival step "
              f"{max(r.arrival_step for r in trace)})")
    if cfg.frontend:
        key = jax.random.PRNGKey(args.seed)
        for i, r in enumerate(trace):
            r.frontend = np.asarray(jax.random.normal(
                jax.random.fold_in(key, i),
                (cfg.frontend_tokens, cfg.d_model), jnp.bfloat16))
    max_len = args.max_len or max(r.prompt_len + r.max_new_tokens
                                  for r in trace)
    injector = (FaultInjector.parse(args.fault_spec, seed=args.seed)
                if args.fault_spec else None)
    engine = Engine(cfg, params, max_batch=args.max_batch, max_len=max_len,
                    backend=backend, scheduler=Scheduler(args.policy),
                    kv_layout=args.kv_layout, page_size=args.page_size,
                    num_pages=args.num_pages,
                    prefill_chunk=args.prefill_chunk,
                    speculate=speculate, draft_k=args.draft_k,
                    slo_routes=slo_routes, sampling=sampling,
                    max_queue_depth=args.max_queue_depth,
                    page_watermark=args.page_watermark,
                    request_timeout_s=args.request_timeout,
                    degrade_to=args.degrade_to,
                    ttft_target_s=args.ttft_target_s,
                    injector=injector)
    results = engine.run(trace)
    for r in results:
        if isinstance(r, ShedResult):
            print(f"[serve]  {r.rid}: SHED ({r.reason}) at step "
                  f"{r.shed_step} after {r.waited_s * 1e3:.0f}ms")
            continue
        print(f"[serve]  {r.rid}: prompt={r.prompt_len} "
              f"gen={r.n_tokens} ({r.finish_reason}) "
              f"ttft={r.ttft_s * 1e3:.0f}ms "
              f"decode={r.decode_tok_s:.1f} tok/s")
    summ = summarize(results, engine.stats["wall_s"])
    print(f"[serve] engine[{args.policy}] B={args.max_batch} "
          f"max_len={max_len}: {summ['total_tokens']} tokens in "
          f"{summ['wall_s'] * 1e3:.0f}ms ({summ['total_tok_s']} tok/s, "
          f"ttft p50 {summ['ttft_p50_s'] * 1e3:.0f}ms / "
          f"p95 {summ['ttft_p95_s'] * 1e3:.0f}ms, "
          f"{engine.stats['decode_steps']} decode steps)")
    st = engine.stats
    if (st["preemptions"] or st["shed_requests"] or st["timeouts"]
            or st["faults_injected"] or st["degrade_transitions"]
            or args.policy == "deadline" or injector is not None
            or args.max_queue_depth or args.page_watermark
            or args.request_timeout):
        print(f"[serve] robustness: preemptions={st['preemptions']} "
              f"resumes={st['resumes']} sheds={st['shed_requests']} "
              f"shed_rate={summ['shed_rate']} timeouts={st['timeouts']} "
              f"faults_injected={st['faults_injected']} "
              f"faults_detected={st['faults_detected']} "
              f"heartbeat_trips={st['heartbeat_trips']} "
              f"degrade_transitions={st['degrade_transitions']} "
              f"degrade_rate={summ['degrade_rate']}")
        if "shed_reasons" in summ:
            print(f"[serve] shed reasons: "
                  + " ".join(f"{k}:{v}" for k, v in
                             sorted(summ["shed_reasons"].items())))
        for step_t, kind, p95 in engine.degrade_log:
            print(f"[serve] degrade transition @step {step_t}: {kind} "
                  f"(window p95 ttft {p95 * 1e3:.0f}ms)")
    if args.check_preempt_parity:
        # replay the SAME trace FCFS without preemption/faults/sheds and
        # compare every COMPLETED request's token stream — preemption must
        # be a pure scheduling decision, invisible in the tokens
        ref_engine = Engine(
            cfg, params, max_batch=args.max_batch, max_len=max_len,
            backend=backend, scheduler=Scheduler("continuous"),
            kv_layout=args.kv_layout, page_size=args.page_size,
            num_pages=args.num_pages, prefill_chunk=args.prefill_chunk,
            slo_routes=slo_routes, sampling=sampling)
        ref = {r.rid: r for r in ref_engine.run(trace)}
        # timed-out requests carry a clean PREFIX of the full stream, so
        # every non-shed result must prefix-match its FCFS replay
        done = [r for r in results if not isinstance(r, ShedResult)]
        bad = [r.rid for r in done
               if isinstance(ref.get(r.rid), ShedResult)
               or r.tokens != ref[r.rid].tokens[:len(r.tokens)]]
        print(f"[serve] preemption token parity "
              f"({len(done)} completed requests): {not bad}")
        if bad:
            print(f"[serve] ERROR: preempted serving diverged from FCFS "
                  f"replay on requests {bad}", file=sys.stderr)
            sys.exit(2)
    if args.kv_layout == "paged":
        st = engine.stats
        print(f"[serve] paged kv: page_size={engine.page_size} "
              f"pool={engine.num_pages} pages "
              f"kv_peak_pages={st['kv_peak_pages']} "
              f"kv_peak_bytes={st['kv_peak_bytes']} "
              f"(capacity {st['kv_capacity_bytes']}) "
              f"prefix_hit_tokens={st['prefix_hit_tokens']} "
              f"prefix_hit_requests={st['prefix_hit_requests']} "
              f"(lookups {st['prefix_lookups']}) "
              f"cow_copies={st['cow_copies']} "
              f"evictions={st['page_evictions']}")
    if "by_slo" in summ:
        for cls, rec in sorted(summ["by_slo"].items()):
            variant = (slo_routes or {}).get(cls, "default")
            print(f"[serve] slo {cls!r} -> variant {variant!r}: "
                  f"{rec['requests']} requests "
                  f"ttft p50 {rec['ttft_p50_s'] * 1e3:.0f}ms / "
                  f"p95 {rec['ttft_p95_s'] * 1e3:.0f}ms, "
                  f"decode p50 {rec['decode_tok_s_p50']} tok/s")
    if speculate is not None:
        st = engine.stats
        print(f"[serve] speculative(draft_k={args.draft_k}): "
              f"rounds={st['spec_rounds']} drafted={st['spec_drafted']} "
              f"accepted={st['spec_accepted']} "
              f"acceptance={st['spec_acceptance']} "
              f"tokens_per_round={st['spec_tokens_per_round']}")
        if args.check_spec_parity:
            # replay the SAME trace target-only (the PlanSet default is the
            # target variant) and compare every request's token stream
            ref_engine = Engine(
                cfg, params, max_batch=args.max_batch, max_len=max_len,
                backend=backend, scheduler=Scheduler(args.policy),
                kv_layout=args.kv_layout, page_size=args.page_size,
                num_pages=args.num_pages, prefill_chunk=args.prefill_chunk)
            ref = ref_engine.run(trace)
            identical = all(a.tokens == b.tokens
                            for a, b in zip(results, ref))
            print(f"[serve] spec tokens identical to target-only: "
                  f"{identical}")
            if not identical:
                bad = [a.rid for a, b in zip(results, ref)
                       if a.tokens != b.tokens]
                print(f"[serve] ERROR: speculative decode diverged from "
                      f"target-only on requests {bad}", file=sys.stderr)
                sys.exit(2)
    return results, summ


# --------------------------------------------------------------------------
# CNN serving (arch "cnn:<config>"): batch inference through the planned
# conv/dense kernels
# --------------------------------------------------------------------------

def serve_cnn(args, cnn_name: str):
    """Batch-inference "serving" of a CNN façade, with ``--mapping`` running
    every bound conv/dense through its planned kernel (im2col'd conv
    lowering) under ``jax.jit``."""
    from repro.models import cnn as C
    cfg = C.get_config(cnn_name)
    init_fn, apply_fn, _ = C.get_model(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = init_fn(key, cfg, None)

    backend = None
    if args.mapping:
        from repro.api import MappingArtifact
        from repro.runtime import ExecutionError, LoweringError
        art = MappingArtifact.load(args.mapping)
        try:
            plan, backend = plan_mapping_execution(params, art)
        except (LoweringError, ExecutionError) as e:
            print(f"[serve] mapping {args.mapping} failed to lower/bind "
                  f"({e})", file=sys.stderr)
            sys.exit(2)
        print(f"[serve] mapping {args.mapping}: model={art.model} "
              f"platform={art.platform}")
        print_plan_coverage("serve", plan, backend)

    x = jax.random.normal(key, (args.requests, *cfg.img_hw, cfg.in_ch),
                          jnp.float32)
    fwd = jax.jit(lambda p, xb: apply_fn(p, xb, cfg, None, "fp", 1.0))
    ctx = matmul_backend(backend) if backend is not None \
        else contextlib.nullcontext()
    with ctx:
        t0 = time.monotonic()
        logits = jax.block_until_ready(fwd(params, x))
        dt = time.monotonic() - t0
    assert logits.shape == (args.requests, cfg.n_classes)
    assert np.isfinite(np.asarray(logits)).all()
    if backend is not None:
        check_coverage("serve", backend, args.require_full_coverage)
    print(f"[serve] {cfg.name}: {args.requests} images in {dt*1e3:.0f}ms "
          f"({args.requests / max(dt, 1e-9):.1f} img/s)")
    return logits, {"forward_s": dt}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    help="LM arch name, or cnn:<config> for CNN façades")
    ap.add_argument("--reduce", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", action="store_true",
                    help="serve through the continuous-batching engine "
                         "(repro.serving): mixed-length trace replay with "
                         "slot admission/retirement + per-request TTFT")
    ap.add_argument("--trace", default=None,
                    help="JSONL request trace for --engine "
                         "(repro.serving.trace format); default: a seeded "
                         "synthetic mixed-length trace")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="engine slot-pool size (concurrent requests)")
    ap.add_argument("--max-len", type=int, default=None,
                    help="engine per-slot sequence capacity (default: "
                         "longest prompt+gen in the trace)")
    ap.add_argument("--policy", default="continuous",
                    choices=["continuous", "static", "deadline"],
                    help="engine admission policy (static = gang batching "
                         "baseline; deadline = priority/slack ordering "
                         "with mid-decode preemption)")
    ap.add_argument("--priorities", default=None,
                    help="synthetic trace: comma-separated ints assigned "
                         "round-robin as request priorities (higher = more "
                         "urgent, used by --policy deadline)")
    ap.add_argument("--deadlines-ms", default=None,
                    help="synthetic trace: comma-separated per-request "
                         "deadlines in ms assigned round-robin ('none' "
                         "for no deadline)")
    ap.add_argument("--poisson", type=float, default=None, metavar="RATE",
                    help="restamp arrivals as a seeded open-loop Poisson "
                         "process at RATE requests per engine step")
    ap.add_argument("--max-queue-depth", type=int, default=None,
                    help="shed the newest waiting requests once the "
                         "admission queue exceeds this depth")
    ap.add_argument("--page-watermark", type=float, default=None,
                    help="paged layout: shed waiting requests when the "
                         "free-page fraction drops below this watermark")
    ap.add_argument("--request-timeout", type=float, default=None,
                    metavar="SECONDS",
                    help="per-request wall-clock budget: queued requests "
                         "shed, running requests retire with their partial "
                         "tokens (finish_reason='timeout')")
    ap.add_argument("--fault-spec", default=None,
                    help="inject seeded faults: comma-separated "
                         "kind@step:slot[xN] events and/or kind~rate "
                         "Bernoulli rates (kinds: nonfinite_logits, "
                         "corrupt_page, stuck)")
    ap.add_argument("--degrade-to", default=None, metavar="CLASS",
                    help="graceful degradation: route NEW requests to this "
                         "--slo-variant class while the sliding p95 TTFT "
                         "exceeds --ttft-target-s")
    ap.add_argument("--ttft-target-s", type=float, default=None,
                    help="p95 TTFT target (seconds) driving --degrade-to")
    ap.add_argument("--check-preempt-parity", action="store_true",
                    help="after a --policy deadline run, replay the trace "
                         "FCFS without preemption and exit nonzero unless "
                         "every completed request's tokens prefix-match")
    ap.add_argument("--kv-layout", default="paged",
                    choices=["paged", "dense"],
                    help="KV-cache layout: paged (block-table pool with "
                         "chunked prefill + prefix caching) or dense "
                         "(B x max_len slots, the parity oracle)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged layout: tokens per KV page")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="paged layout: pool capacity in pages (default "
                         "max_batch * ceil(max_len / page_size))")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="paged layout: prompt tokens prefilled per engine "
                         "step (default 2 * page_size)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="synthetic --engine trace: prepend the same "
                         "N-token system prefix to every prompt (exercises "
                         "prefix caching)")
    ap.add_argument("--speculate", default=None, metavar="DRAFT_MAPPING",
                    help="second mapping artifact of the SAME weights bound "
                         "as the 'draft' variant of a PlanSet bank "
                         "(--mapping is the 'target'): self-speculative "
                         "decoding, token-identical to target-only greedy")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="tokens drafted per speculative round")
    ap.add_argument("--check-spec-parity", action="store_true",
                    help="after the speculative run, replay the trace "
                         "target-only and exit nonzero unless every "
                         "request's tokens are identical")
    ap.add_argument("--slo-variant", action="append", default=[],
                    metavar="CLASS=MAPPING",
                    help="route SLO class CLASS to a variant bound from "
                         "this mapping artifact (repeatable; --mapping is "
                         "the default variant for unrouted requests)")
    ap.add_argument("--temperature", type=float, default=None,
                    help="enable non-greedy sampling at this temperature "
                         "(default: greedy argmax)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (enables sampling when "
                         "< 1.0)")
    ap.add_argument("--mapping", default=None,
                    help="mapping artifact JSON (repro.api schema); lowered "
                         "to per-layer ExecutionPlans, with the global "
                         "majority-dtype path as fallback")
    ap.add_argument("--mapping-fallback", action="store_true",
                    help="skip plan lowering and use the legacy global "
                         "majority-dtype path directly")
    ap.add_argument("--require-full-coverage", action="store_true",
                    help="exit nonzero unless every planned layer is bound "
                         "AND executes as mapped (no fp fallbacks, no "
                         "trace-time declines)")
    args = ap.parse_args(argv)

    if args.require_full_coverage and not args.mapping:
        # without an artifact nothing executes as mapped — passing the gate
        # green would be exactly the silent fallback it exists to catch
        ap.error("--require-full-coverage needs --mapping")

    robust_flags = (args.policy == "deadline" or args.poisson
                    or args.max_queue_depth or args.page_watermark
                    or args.request_timeout or args.fault_spec
                    or args.degrade_to or args.check_preempt_parity
                    or args.priorities or args.deadlines_ms)
    if robust_flags and not args.engine:
        ap.error("robustness flags (--policy deadline / --poisson / "
                 "--max-queue-depth / --page-watermark / --request-timeout "
                 "/ --fault-spec / --degrade-to / --check-preempt-parity / "
                 "--priorities / --deadlines-ms) need --engine")
    if args.degrade_to:
        if args.ttft_target_s is None:
            ap.error("--degrade-to needs --ttft-target-s")
        if not any(s.startswith(f"{args.degrade_to}=")
                   for s in args.slo_variant):
            ap.error(f"--degrade-to {args.degrade_to!r} must name a "
                     f"--slo-variant class of the bank")
    elif args.ttft_target_s is not None:
        ap.error("--ttft-target-s needs --degrade-to")
    if args.check_preempt_parity and args.policy != "deadline":
        ap.error("--check-preempt-parity needs --policy deadline")

    args.slo_classes = []
    if args.speculate or args.slo_variant:
        if not args.engine:
            ap.error("--speculate/--slo-variant need --engine")
        if not args.mapping:
            ap.error("--speculate/--slo-variant need --mapping (the "
                     "target/default plan of the bank)")
        if args.mapping_fallback:
            ap.error("--mapping-fallback cannot serve a multi-plan bank")
        if args.speculate and args.slo_variant:
            ap.error("--speculate and --slo-variant are mutually exclusive")
    for spec_arg in args.slo_variant:
        cls, sep, path = spec_arg.partition("=")
        if not cls or not sep or not path:
            ap.error(f"--slo-variant wants CLASS=MAPPING, got {spec_arg!r}")
        args.slo_classes.append(cls)

    if args.arch.startswith("cnn:"):
        if args.engine:
            ap.error("--engine is a decode-loop mode; CNN façades have no "
                     "KV cache to batch continuously")
        return serve_cnn(args, args.arch.split(":", 1)[1])

    cfgbase.load_all()
    cfg = cfgbase.get(args.arch)
    if args.reduce:
        cfg = cfgbase.reduce_for_smoke(cfg)

    art = None
    if args.mapping:
        from repro.api import MappingArtifact
        art = MappingArtifact.load(args.mapping)

    key = jax.random.PRNGKey(args.seed)
    params = T.init_lm(key, cfg)

    backend = None
    if art is not None and (args.speculate or args.slo_variant):
        from repro.api import MappingArtifact
        from repro.runtime import ExecutionError, LoweringError
        if args.speculate:
            arts = {"target": art,
                    "draft": MappingArtifact.load(args.speculate)}
            default = "target"
        else:
            arts = {"default": art}
            for spec_arg in args.slo_variant:
                cls, _, path = spec_arg.partition("=")
                arts[cls] = MappingArtifact.load(path)
            default = "default"
        try:
            plans, backend = build_planset(params, arts, default)
        except (LoweringError, ExecutionError) as e:
            print(f"[serve] multi-plan bank failed to lower/bind ({e})",
                  file=sys.stderr)
            sys.exit(2)
        # the default/target artifact sets the KV cache, as on the
        # single-plan path
        cfg = planned_kv_cfg(cfg, art)
        print(f"[serve] planset bank: model={art.model} "
              f"platform={art.platform} "
              f"variants={list(backend.variant_names)} default={default!r} "
              f"kv={cfg.kv_cache_dtype} (jit: prefill+decode)")
        print_planset_report("serve", plans, backend)
    elif art is not None:
        from repro.runtime import ExecutionError, LoweringError
        plan = None
        if not args.mapping_fallback:
            t0 = time.perf_counter()
            try:
                plan, backend = plan_mapping_execution(params, art)
            except (LoweringError, ExecutionError) as e:
                print(f"[serve] mapping {args.mapping} failed to lower/bind "
                      f"({e}); falling back to majority-dtype serving")
            else:
                print(f"[serve] lowered and bound in "
                      f"{time.perf_counter() - t0:.3f}s")
        if backend is not None:
            cfg = planned_kv_cfg(cfg, art)
            print(f"[serve] mapping {args.mapping}: model={art.model} "
                  f"platform={art.platform} kv={cfg.kv_cache_dtype} "
                  f"(jit: prefill+decode)")
            print_plan_coverage("serve", plan, backend)
        else:
            cfg, dom = apply_mapping_artifact(cfg, art)
            print(f"[serve] mapping {args.mapping}: model={art.model} "
                  f"platform={art.platform} FALLBACK majority domain="
                  f"{dom['name']} -> weights={cfg.serve_weight_dtype} "
                  f"kv={cfg.kv_cache_dtype}")
            if args.require_full_coverage:
                print("[serve] ERROR: --require-full-coverage but no "
                      "execution plan could be bound", file=sys.stderr)
                sys.exit(2)

    if args.engine:
        results, summ = serve_engine(args, cfg, params, backend=backend)
        if backend is not None:
            check_coverage("serve", backend, args.require_full_coverage)
        return results, summ

    prompts = jax.random.randint(key, (args.requests, args.prompt_len),
                                 0, cfg.vocab)
    frontend = None
    if cfg.frontend:
        frontend = jax.random.normal(
            key, (args.requests, cfg.frontend_tokens, cfg.d_model),
            jnp.bfloat16)
    gen, stats = serve_batch(cfg, params, prompts, args.gen_len, frontend,
                             backend=backend)
    assert gen.shape == (args.requests, args.gen_len)
    assert np.isfinite(np.asarray(gen)).all()
    if backend is not None:
        check_coverage("serve", backend, args.require_full_coverage)
    print(f"[serve] {cfg.name}: {args.requests} reqs, prefill "
          f"{stats['prefill_s']*1e3:.0f}ms, decode {stats['decode_s']*1e3:.0f}ms "
          f"({stats['tok_per_s']:.1f} tok/s)")
    print("[serve] sample generations:", np.asarray(gen[:2, :8]))
    return gen, stats


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
