"""`Engine`: continuous-batching inference over (optionally planned) LMs.

One engine owns a fixed pool of ``max_batch`` decode slots and runs the
standard continuous-batching loop (ADMIT -> PREFILL -> DECODE -> RETIRE).
Two KV layouts back the slots:

``kv_layout="paged"`` (default) — the vLLM-style BLOCK-TABLE layout:
  * KV lives in a SHARED pool of ``num_pages`` fixed-size pages
    (`transformer.init_paged_cache`; row 0 is a trash page for masked
    writes).  Each slot maps logical positions to pages through a
    ``(W,)`` int32 page-table row; attention gathers the slot's pages into
    a contiguous view and the existing ``q_pos0``/``kv_len`` per-slot
    masking applies unchanged.  Peak KV memory scales with TOKENS IN
    FLIGHT, not B x worst-case max_len.
  * CHUNKED PREFILL: prompts stream into their pages ``prefill_chunk``
    tokens per engine step, interleaved with decode steps of the other
    slots, so a long prompt neither stalls the batch nor needs a
    monolithic prefill trace.  Admission requires "fits in free pages"
    (per-request reservation of ceil(min(prompt+budget, W*page_size) /
    page_size) pages), not ``prompt_len < max_len``.  Recurrent (SSM /
    xLSTM) state carries across chunks exactly — masked steps are
    identities — so hybrid archs chunk-prefill too.
  * PREFIX CACHING: once a prompt's pages are written they are registered
    under exact token-prefix keys; a later request whose prompt shares the
    prefix maps the SAME pages (copy-on-write for a partially covered tail
    page) and prefills only its unique suffix.  Enabled automatically for
    attention-only, non-MoE, frontend-free archs — recurrent state is not
    page-resident and MoE dispatch is batch-dependent, so sharing would be
    unsound there.  SLO routing disables it too: routed variants write
    variant-specific KV numerics, so pages could not be shared across
    classes.

``kv_layout="dense"`` — the PR-5 layout kept as the parity oracle: B slots
of ``max_len`` dense KV, one-shot ragged prefill per admission group
(bucketed prompt length AND group size, so mixed traffic retraces prefill
at most O(log^2) times), `transformer.scatter_cache` admission.

The decode step traces ONCE per layout (fixed pool shapes; the paged chunk
step likewise traces once).  With a `repro.runtime.PlannedBackend` passed
as ``backend``, every jitted call executes covered projections through
their planned split-precision kernels (the name-keyed matmul-backend
protocol resolves statically inside jit), so engine latency IS mapped
latency.

MULTI-PLAN SERVING — with a `repro.runtime.PlanSet` bound as ``backend``
(N precision variants over ONE shared params pytree), the engine can
exploit the variants at serving time:

  * SELF-SPECULATIVE DECODING (``speculate=(draft, target)``): every
    decode round drafts ``draft_k`` greedy tokens per slot with the cheap
    ``draft`` variant (a `lax.scan` over the paged decode step), then
    verifies all of them in ONE fixed-shape `prefill_chunk` call under the
    ``target`` variant (``full_logits=True`` recovers the per-position
    argmax), accepting the longest prefix where draft and target agree
    plus one bonus target token.  Verify overwrites every draft-written
    KV position with target numerics, so the committed cache is exactly
    the target-only cache; for hybrid (recurrent) archs a replay chunk
    restores the pre-round recurrent state of partially-accepting slots
    and re-advances it over the committed tokens only.  Output is
    TOKEN-IDENTICAL to target-only greedy decoding (requires static
    activation scales — see Exactness notes).  Paged-only, greedy-only,
    non-MoE, frontend-free.
  * SLO ROUTING (``slo_routes={"interactive": "draft", ...}``): each
    request's SLO class picks the plan variant serving it.  Decode and
    chunked prefill run once per ACTIVE variant group with the other
    slots masked (masked paged writes land in the trash page, so groups
    cannot corrupt each other's KV); a request's entire KV is written
    under its own variant, keeping per-request numerics identical to
    serving it alone under that variant.  Paged-only.
  * NON-GREEDY SAMPLING (``sampling=SamplingParams(...)``): temperature /
    top-p sampling as jit-safe per-slot state — see `repro.serving
    .sampling`.  OFF by default (argmax, bit-identical to before).

Exactness notes: outputs are token-identical to per-request serving for
every non-MoE arch (padding/masking is exact — see the `repro.serving`
package docstring for the MoE capacity caveat), provided the bound plan
uses STATIC activation scales; dynamic max-abs activation quantization is
computed over the whole pooled batch and therefore depends on batch
composition (this is also why speculative verify, whose batch rows differ
from sequential decode's, requires static scales for token identity).

ROBUSTNESS LAYER (paged layout) — the engine stays on its SLO under
overload and numerical faults instead of degrading unboundedly:

  * DEADLINE SCHEDULING + PREEMPTION (``Scheduler(policy="deadline")``):
    admission is ordered by `repro.serving.scheduler.urgency` (priority,
    then deadline slack) instead of FCFS, and when a waiting request is
    strictly more urgent than the least-urgent running one (and no free
    slot/pages can serve it) the victim slot is RETIRE-AND-REQUEUED: its
    committed tokens are recorded, its pages are released — hashed prefix
    pages park in the `PagePool` LRU, still matchable — and the request
    resumes later by prefilling ``original prompt + committed tokens``,
    which by the prefill/decode logit-equality invariant reproduces the
    exact decode state, so the final token stream is IDENTICAL to an
    unpreempted run (greedy + static scales, like all parity guarantees
    here).  With the prefix cache on, resumption re-prefills only the
    unhashed tail.  At most one preemption fires per step and each
    request is preempted at most ``max_preemptions`` times.
  * LOAD SHEDDING (``max_queue_depth`` / ``page_watermark`` /
    ``request_timeout_s``): instead of queueing without bound, excess
    visible requests are rejected with a structured
    `repro.serving.metrics.ShedResult` — newest-first beyond the queue
    depth, everything behind the head of line when the free-page
    fraction drops below the watermark, and any request (queued OR
    running) that outlives the timeout (running requests retire with
    their partial tokens and ``finish_reason="timeout"``).
  * PRECISION DEGRADATION (``degrade_to=variant, ttft_target_s=...``):
    a sliding p95 over observed TTFTs; on breach, NEW admissions route to
    the cheaper `PlanSet` variant (the paper's accuracy axis spent to buy
    back latency), and route back once p95 recovers below a hysteresis
    fraction of the target.  Every transition is recorded
    (``degrade_log`` / ``stats["degrade_transitions"]``); requests served
    degraded carry ``RequestResult.degraded=True``.
  * FAULT CONTAINMENT (``injector=FaultInjector(...)``): every decode /
    chunk step returns a ``jnp.isfinite`` screen over its logits; a slot
    whose logits go non-finite commits NOTHING that step — its pages are
    purged from the prefix cache (corruption must never be re-matched),
    the slot is quarantined for ``quarantine_steps``, and the request is
    requeued ONCE with its (clean) committed tokens; a second fault sheds
    it with ``ShedResult(reason="fault")``.  Stuck slots — which commit
    nothing, so the logit screen cannot see them — are caught by the
    `repro.distributed.fault_tolerance.HeartbeatMonitor` running on the
    engine's step clock (slots beat on token commit / chunk progress).

SPANS — each phase of the loop runs inside `Engine._span`, which writes a
`jax.profiler.TraceAnnotation` (under a profiler it lands on the host
plane of the device trace, on its clock) and adds the phase's wall time to
``stats`` under the key `span_key` derives from the name
(``engine.decode.wait`` -> ``engine_decode_wait_s``).  The tree, the same
on every path:

  engine.run
    engine.step               one loop iteration
      engine.schedule         timeouts, preemption, admissions, shedding,
                              fault injection
      engine.admit            slot bookkeeping, page allocation, slot reset
                              and copy-on-write dispatches (dense: the
                              prompt batch)
      engine.chunk            prompt prefill (chunked; dense: one call)
        engine.chunk.dispatch   the jitted calls returning
        engine.chunk.wait       host syncs on their outputs
      engine.decode           one decode step (speculative: one round)
        engine.decode.dispatch
        engine.decode.wait
        engine.decode.commit  token commit, retirement, fault handling

``stats["decode_rows"]`` counts the slots each decode call ran, and
``RequestResult.queue_s`` is a request's wait for its first admission.
"""
from __future__ import annotations

import contextlib
import math
import time
import warnings
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.fault_tolerance import HeartbeatMonitor
from repro.models import transformer as T
from repro.models.managed import matmul_backend
from repro.serving.batch import BatchState
from repro.serving.faults import FaultInjector
from repro.serving.metrics import RequestResult, ShedResult, percentile
from repro.serving.paged import PagePool
from repro.serving.sampling import SamplingParams, request_key, sample_tokens
from repro.serving.scheduler import Request, RequestQueue, Scheduler, urgency

EngineResult = Union[RequestResult, ShedResult]

KV_LAYOUTS = ("paged", "dense")

# prefix sharing is only sound when ALL sequence state is page-resident
# (pure attention KV) and per-token compute is batch-composition-free
_PREFIX_SAFE_KINDS = frozenset({"attn", "shared_attn", "mla"})


def span_key(name: str) -> str:
    """The ``Engine.stats`` key of span ``name``: ``engine.decode.wait``
    -> ``engine_decode_wait_s``."""
    return name.replace(".", "_") + "_s"


class _Span:
    """`Engine._span`'s context manager; a plain class because a
    generator-based one costs about twice as much per span."""
    __slots__ = ("stats", "key", "ann", "t0")

    def __init__(self, stats: Dict[str, float], name: str):
        self.stats = stats
        self.key = span_key(name)
        self.ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self.ann.__enter__()
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        self.ann.__exit__(*exc)
        self.stats[self.key] = self.stats.get(self.key, 0.0) + dt


class Engine:
    """Continuous-batching serving engine (see module docstring).

    Parameters:
      cfg, params   — the LM (`repro.configs` ArchConfig + its weights).
      max_batch     — pool size B (concurrent requests).
      max_len       — per-slot sequence capacity: dense slots hold exactly
                      ``max_len`` tokens; paged slots hold ``W * page_size``
                      with W = ceil(max_len / page_size) (requests beyond
                      that retire as "length_cap").
      backend       — optional matmul backend (e.g. `PlannedBackend` /
                      `PlanSet`) installed around every jitted call.
      scheduler     — a `Scheduler` (default: continuous policy).
      prefill_bucket— dense layout: minimum prompt padding; group prompt
                      lengths round up to the next power-of-two multiple of
                      it (bounds prefill retraces).
      kv_layout     — "paged" (default) or "dense" (see module docstring).
      page_size     — paged: tokens per KV page (16 default — a multiple of
                      typical attention block tiles, small enough that a
                      short request wastes < page_size tokens per slot).
      num_pages     — paged: pool capacity (default B * W: same worst-case
                      capacity as dense; undercommit for memory savings,
                      overcommit for longer admission queues).
      prefill_chunk — paged: prompt tokens per chunked-prefill step
                      (default 2 * page_size).
      prefix_cache  — paged: hash-share prompt pages across requests
                      (auto-disabled for archs where sharing is unsound).
      speculate     — optional ``(draft_variant, target_variant)`` pair of
                      variant names on the bound `PlanSet`: enables
                      self-speculative decoding (see module docstring).
      draft_k       — tokens drafted per speculative round (default 4).
      slo_routes    — optional ``{slo_class: variant_name}`` map routing
                      each request's SLO class to a plan variant.
      sampling      — optional `SamplingParams`; None = greedy (default).

    Robustness (see the ROBUSTNESS LAYER section of the module docstring;
    all of these are paged-only except the queue-level sheds/timeouts):
      max_queue_depth  — shed (``ShedResult(reason="queue_depth")``) the
                         newest visible queued requests beyond this depth.
      page_watermark   — fraction in (0, 1]: when free pages drop below it,
                         shed every visible queued request behind the head
                         of line (``reason="page_watermark"``).
      request_timeout_s— wall-clock budget per request measured from when
                         it became schedulable: queued requests shed
                         (``reason="timeout"``), running requests retire
                         with partial tokens (``finish_reason="timeout"``).
      max_preemptions  — per-request retire-and-requeue cap under the
                         deadline policy (bounds preemption thrash).
      degrade_to       — `PlanSet` variant name new admissions route to
                         while the TTFT p95 estimate breaches
                         ``ttft_target_s`` (required together; hysteresis
                         recovery at ``degrade_recover_frac * target``).
      injector         — optional `repro.serving.faults.FaultInjector`.
      quarantine_steps — steps a slot sits out after a detected fault.
      heartbeat_steps  — step-clock deadline for the stuck-slot monitor.
    """

    def __init__(self, cfg, params, *, max_batch: int = 8, max_len: int = 64,
                 backend=None, scheduler: Optional[Scheduler] = None,
                 prefill_bucket: int = 8, kv_layout: str = "paged",
                 page_size: int = 16, num_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: bool = True,
                 speculate: Optional[Tuple[str, str]] = None,
                 draft_k: int = 4,
                 slo_routes: Optional[Dict[str, str]] = None,
                 sampling: Optional[SamplingParams] = None,
                 max_queue_depth: Optional[int] = None,
                 page_watermark: Optional[float] = None,
                 request_timeout_s: Optional[float] = None,
                 max_preemptions: int = 2,
                 degrade_to: Optional[str] = None,
                 ttft_target_s: Optional[float] = None,
                 degrade_window: int = 8,
                 degrade_recover_frac: float = 0.7,
                 injector: Optional[FaultInjector] = None,
                 quarantine_steps: int = 2,
                 heartbeat_steps: int = 32):
        if kv_layout not in KV_LAYOUTS:
            raise ValueError(f"kv_layout must be one of {KV_LAYOUTS}, "
                             f"got {kv_layout!r}")
        self.cfg = cfg
        self.params = params
        self.max_batch = int(max_batch)
        self.max_len = int(max_len)
        self.backend = backend
        self.scheduler = scheduler or Scheduler()
        self.prefill_bucket = max(1, int(prefill_bucket))
        self.kv_layout = kv_layout
        self.sampling = sampling
        self.draft_k = int(draft_k)
        self._spec = tuple(speculate) if speculate is not None else None
        self.slo_routes = dict(slo_routes) if slo_routes else None
        self.max_queue_depth = max_queue_depth
        self.page_watermark = page_watermark
        self.request_timeout_s = request_timeout_s
        self.max_preemptions = int(max_preemptions)
        self.degrade_to = degrade_to
        self.injector = injector
        self.quarantine_steps = int(quarantine_steps)
        self.heartbeat_steps = int(heartbeat_steps)
        if max_queue_depth is not None and max_queue_depth < 1:
            raise ValueError(f"max_queue_depth must be >= 1, "
                             f"got {max_queue_depth}")
        if page_watermark is not None and not 0.0 < page_watermark <= 1.0:
            raise ValueError(f"page_watermark must be in (0, 1], "
                             f"got {page_watermark}")
        if request_timeout_s is not None and request_timeout_s < 0:
            raise ValueError(f"request_timeout_s must be >= 0, "
                             f"got {request_timeout_s}")
        if (degrade_to is None) != (ttft_target_s is None):
            raise ValueError("degrade_to and ttft_target_s come together: "
                             "the degraded variant needs a TTFT target to "
                             "defend (and vice versa)")
        self._degrade = (_DegradeController(ttft_target_s,
                                            window=degrade_window,
                                            recover_frac=degrade_recover_frac)
                         if degrade_to is not None else None)
        self.degrade_log = self._degrade.transitions if self._degrade else []
        self.stats: Dict[str, float] = {}
        # python-side counters bumped inside the traced function bodies:
        # they count TRACES, not calls (tests pin the retrace bound)
        self.trace_counts = {"prefill": 0, "decode": 0, "chunk": 0,
                             "draft": 0, "verify": 0, "replay": 0}

        variant_names = getattr(backend, "variant_names", None)
        if self._spec is not None:
            if len(self._spec) != 2 or not all(
                    isinstance(v, str) for v in self._spec):
                raise ValueError(
                    f"speculate must be a (draft_variant, target_variant) "
                    f"pair of variant names, got {speculate!r}")
            if kv_layout != "paged":
                raise ValueError(
                    "speculative decoding requires kv_layout='paged': the "
                    "dense layout writes garbage KV at masked slots' live "
                    "positions, so draft/verify masking would corrupt "
                    "co-batched state (paged masked writes hit the trash "
                    "page)")
            if cfg.moe is not None:
                raise ValueError(
                    "speculative decoding is unsupported for MoE archs: "
                    "expert dispatch is batch-composition-dependent, so "
                    "verify logits would not match sequential decoding")
            if cfg.frontend:
                raise ValueError(
                    "speculative decoding is unsupported for frontend "
                    "(cross-attention) archs")
            if sampling is not None:
                raise ValueError(
                    "speculative decoding is greedy-only (its token-"
                    "identity guarantee is an argmax property); drop "
                    "`sampling` or `speculate`")
            if slo_routes:
                raise ValueError(
                    "speculate and slo_routes are mutually exclusive: "
                    "speculation pins every slot to the draft/target pair")
            if self.draft_k < 1:
                raise ValueError(f"draft_k must be >= 1, got {draft_k}")
            if variant_names is None:
                raise ValueError(
                    "speculate needs a multi-variant PlanSet backend "
                    "(`repro.runtime.PlanSet`); got "
                    f"{type(backend).__name__ if backend is not None else None}")
            for v in self._spec:
                if v not in variant_names:
                    raise ValueError(
                        f"speculate variant {v!r} is not bound: this "
                        f"PlanSet has {list(variant_names)}")
        if self.slo_routes:
            if kv_layout != "paged":
                raise ValueError(
                    "SLO routing requires kv_layout='paged': variant-"
                    "grouped decode masks the other groups' slots, and "
                    "only the paged layout routes masked KV writes to the "
                    "trash page instead of live positions")
            if variant_names is None:
                raise ValueError(
                    "slo_routes needs a multi-variant PlanSet backend "
                    "(`repro.runtime.PlanSet`); got "
                    f"{type(backend).__name__ if backend is not None else None}")
            for cls, v in self.slo_routes.items():
                if v not in variant_names:
                    raise ValueError(
                        f"slo_routes[{cls!r}] -> {v!r} is not bound: this "
                        f"PlanSet has {list(variant_names)}")
        if self.degrade_to is not None:
            if kv_layout != "paged":
                raise ValueError(
                    "precision degradation requires kv_layout='paged' "
                    "(variant-grouped execution masks into the trash page)")
            if variant_names is None:
                raise ValueError(
                    "degrade_to needs a multi-variant PlanSet backend "
                    "(`repro.runtime.PlanSet`); got "
                    f"{type(backend).__name__ if backend is not None else None}")
            if self.degrade_to not in variant_names:
                raise ValueError(
                    f"degrade_to={self.degrade_to!r} is not bound: this "
                    f"PlanSet has {list(variant_names)}")
        if self.injector is not None and kv_layout != "paged":
            raise ValueError(
                "fault injection requires kv_layout='paged' (containment "
                "releases/purges pages and requeues via chunked prefill)")
        if self._spec is not None and (
                self.injector is not None or self.degrade_to is not None
                or (scheduler is not None and scheduler.preempts)):
            raise ValueError(
                "speculate is incompatible with fault injection, precision "
                "degradation, and deadline preemption: a speculative round "
                "commits multiple tokens under a pinned draft/target pair, "
                "which the per-step containment/routing machinery does not "
                "cover")

        if kv_layout == "paged":
            self.page_size = int(page_size)
            self.pages_per_slot = -(-self.max_len // self.page_size)
            self.slot_cap = self.pages_per_slot * self.page_size
            self.num_pages = (int(num_pages) if num_pages is not None
                              else self.max_batch * self.pages_per_slot)
            self.prefill_chunk = (int(prefill_chunk) if prefill_chunk
                                  else 2 * self.page_size)
            # degrade_to joins slo_routes here: both make KV numerics
            # variant-dependent, so pages cannot be shared across requests
            self.prefix_cache = bool(prefix_cache) and \
                cfg.moe is None and not cfg.frontend and \
                set(cfg.pattern) <= _PREFIX_SAFE_KINDS and \
                not self.slo_routes and self.degrade_to is None
            self.pool_mgr = PagePool(self.num_pages, self.page_size)
            # the DEVICE page pool persists across run() calls: the
            # allocator's hash index outlives a run, so the pages it can
            # match must stay resident too (a repeated trace then serves
            # its prompts straight from cache)
            self._paged_caches = None
        else:
            self.slot_cap = self.max_len
            self.prefix_cache = False

        self._kv_axes = T.cache_kv_axes(cfg)
        self._has_recurrent = any(
            ax.startswith("slot") for ax in jax.tree.leaves(self._kv_axes))
        self._kv_capacity_bytes, self._kv_page_bytes = self._kv_footprint()
        if sampling is not None:
            self._base_key = jax.random.PRNGKey(int(sampling.seed))
        self._req_counter = 0
        # robustness bookkeeping (cleared per run): per-request resume/
        # serving metadata, quarantined-slot release steps, stuck-until
        # markers from the injector
        self._req_meta: Dict[int, dict] = {}
        self._quarantine: Dict[int, int] = {}
        self._stuck: Dict[int, int] = {}
        self._inject_slots: List[int] = []
        self._monitor: Optional[HeartbeatMonitor] = None

        def pick(logits, keys):
            # greedy argmax, or per-slot sampling advancing the PRNG keys
            # (keys ride through unchanged when greedy so trace signatures
            # are sampling-independent)
            if sampling is None:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32), keys
            tok, keys = sample_tokens(logits, keys, sampling)
            return tok, keys

        def decode_fn(params, tok, caches, lengths, active, keys):
            self.trace_counts["decode"] += 1
            logits, caches = T.decode_step(params, cfg, tok, caches, lengths,
                                           active=active)
            tok, keys = pick(logits, keys)
            return tok, keys, caches

        def decode_paged_fn(params, tok, caches, lengths, active, pages,
                            keys, inject, *, variant=None):
            # ``inject`` (B,) float32 is the fault-injection vector (zeros
            # in normal operation; NaN at a targeted slot) — a traced
            # argument, so injecting never retraces.  ``ok`` is the
            # containment screen: True iff the slot's logits are finite.
            self.trace_counts["decode"] += 1
            logits, caches = T.decode_step(params, cfg, tok, caches, lengths,
                                           active=active, pages=pages,
                                           variant=variant)
            logits = logits + inject[:, None]
            ok = jnp.isfinite(logits).all(axis=-1)
            tok, keys = pick(logits, keys)
            return tok, keys, ok, caches

        def prefill_fn(params, prompts, lengths, pool, slots, frontend,
                       keys):
            self.trace_counts["prefill"] += 1
            fresh = T.init_cache(cfg, prompts.shape[0], self.max_len)
            logits, fresh = T.prefill(params, cfg, prompts, fresh,
                                      cross_source=frontend, lengths=lengths)
            tok0, keys = pick(logits, keys)
            return tok0, keys, T.scatter_cache(pool, fresh, slots)

        def chunk_fn(params, tokens, caches, fill, valid, pages, frontend,
                     keys, *, variant=None):
            self.trace_counts["chunk"] += 1
            logits, caches = T.prefill_chunk(params, cfg, tokens, caches,
                                             fill, valid, pages,
                                             cross_source=frontend,
                                             variant=variant)
            # the isfinite screen only means anything for slots completing
            # their prompt this chunk (other rows' logits are unread)
            ok = jnp.isfinite(logits).all(axis=-1)
            tok, keys = pick(logits, keys)
            return tok, keys, ok, caches

        def reset_fn(caches, slots):
            # zero the per-slot (non-page) state of freshly admitted slots:
            # recurrent state and encoder memory must not leak from the
            # slot's previous occupant (dense admission overwrites via
            # scatter_cache instead)
            def f(leaf, ax):
                if ax == "slot0":
                    return leaf.at[slots].set(jnp.zeros((), leaf.dtype))
                if ax == "slot1":
                    return leaf.at[:, slots].set(jnp.zeros((), leaf.dtype))
                return leaf
            return jax.tree.map(f, caches, self._kv_axes)

        def copy_pages_fn(caches, src, dst):
            # copy-on-write: duplicate shared partially-filled tail pages
            # into pages the new request owns before it writes them
            def f(leaf, ax):
                if ax == "page0":
                    return leaf.at[dst].set(leaf[src])
                if ax == "page1":
                    return leaf.at[:, dst].set(leaf[:, src])
                return leaf
            return jax.tree.map(f, caches, self._kv_axes)

        def corrupt_pages_fn(caches, pages):
            # fault injection: stomp NaN over the floating-point KV rows of
            # ``pages`` — the damage surfaces as non-finite logits on the
            # next step that attends over them
            def f(leaf, ax):
                if not jnp.issubdtype(leaf.dtype, jnp.floating):
                    return leaf
                if ax == "page0":
                    return leaf.at[pages].set(jnp.nan)
                if ax == "page1":
                    return leaf.at[:, pages].set(jnp.nan)
                return leaf
            return jax.tree.map(f, caches, self._kv_axes)

        self._decode = jax.jit(decode_fn)
        self._decode_paged = jax.jit(decode_paged_fn,
                                     static_argnames=("variant",))
        self._prefill = jax.jit(prefill_fn)
        self._chunk = jax.jit(chunk_fn, static_argnames=("variant",))
        self._reset = jax.jit(reset_fn)
        self._copy_pages = jax.jit(copy_pages_fn)
        self._corrupt_pages = jax.jit(corrupt_pages_fn)

        if self._spec is not None:
            draft_v, target_v = self._spec
            k = self.draft_k
            cap = self.slot_cap

            def restore_slots(caches, snap, mask=None):
                # put recurrent (slot-resident) state back to its pre-draft
                # snapshot; page pools keep the draft writes (verify
                # overwrites every draft-written position).  ``mask`` (B,)
                # limits the restore to selected slots.
                def f(leaf, s, ax):
                    if not ax.startswith("slot"):
                        return leaf
                    if mask is None:
                        return s
                    shape = ((-1,) + (1,) * (leaf.ndim - 1) if ax == "slot0"
                             else (1, -1) + (1,) * (leaf.ndim - 2))
                    return jnp.where(mask.reshape(shape), s, leaf)
                return jax.tree.map(f, caches, snap, self._kv_axes)

            def draft_fn(params, tok, caches, lengths, active, pages):
                # k greedy decode steps under the DRAFT variant; slots at
                # capacity stop advancing (their rows repeat the carry
                # token — verify's per-slot valid count ignores them)
                self.trace_counts["draft"] += 1
                def body(carry, _):
                    tok, caches, pos = carry
                    live = active & (pos < cap)
                    logits, caches = T.decode_step(
                        params, cfg, tok, caches, pos, active=live,
                        pages=pages, variant=draft_v)
                    nxt = jnp.where(
                        live, jnp.argmax(logits, axis=-1).astype(jnp.int32),
                        tok)
                    return (nxt, caches, pos + live.astype(jnp.int32)), nxt
                init = (tok.astype(jnp.int32), caches,
                        lengths.astype(jnp.int32))
                (_, caches, _), toks = jax.lax.scan(body, init, None,
                                                    length=k)
                return jnp.swapaxes(toks, 0, 1), caches        # (B, k)

            def verify_fn(params, tok0, drafted, caches, snap, fill, valid,
                          pages):
                # one fixed-shape chunk of [t0, d1..dk] under the TARGET
                # variant: full logits give the target argmax at every
                # drafted position, and the chunk's KV writes replace all
                # draft-written positions with target numerics
                self.trace_counts["verify"] += 1
                if self._has_recurrent:
                    caches = restore_slots(caches, snap)
                tokens = jnp.concatenate(
                    [tok0[:, None].astype(jnp.int32), drafted], axis=1)
                logits, caches = T.prefill_chunk(
                    params, cfg, tokens, caches, fill, valid, pages,
                    variant=target_v, full_logits=True)
                return jnp.argmax(logits, axis=-1).astype(jnp.int32), caches

            def replay_fn(params, tok0, drafted, caches, snap, fill, valid,
                          pages):
                # hybrid archs, partial accepts only: rewind the slot's
                # recurrent state to the round snapshot and re-advance it
                # over exactly the committed tokens (valid = c); the KV
                # rewrite is value-identical, recurrent state ends at the
                # sequential S_{L+c}
                self.trace_counts["replay"] += 1
                caches = restore_slots(caches, snap, mask=valid > 0)
                tokens = jnp.concatenate(
                    [tok0[:, None].astype(jnp.int32), drafted], axis=1)
                _, caches = T.prefill_chunk(params, cfg, tokens, caches,
                                            fill, valid, pages,
                                            variant=target_v)
                return caches

            self._draft = jax.jit(draft_fn)
            self._verify = jax.jit(verify_fn)
            self._replay = jax.jit(replay_fn)

    # ---- helpers ---------------------------------------------------------

    def _kv_footprint(self):
        """(total sequence-KV bytes of the pool, bytes per page or None).

        Sums only the sequence-indexed attention-KV leaves (the ``"page"``
        markers of `transformer.cache_kv_axes`) — per-slot recurrent state
        is identical across layouts and excluded so dense-vs-paged peak
        numbers compare exactly what paging changes."""
        if self.kv_layout == "paged":
            specs = T.paged_cache_specs(self.cfg, self.max_batch,
                                        self.num_pages + 1, self.page_size)
        else:
            specs = T.cache_specs(self.cfg, self.max_batch, self.max_len)
        total = 0
        per_page = 0
        for leaf, ax in zip(jax.tree.leaves(specs),
                            jax.tree.leaves(self._kv_axes)):
            if not ax.startswith("page"):
                continue
            nbytes = math.prod(leaf.shape) * leaf.dtype.itemsize
            total += nbytes
            if self.kv_layout == "paged":
                # bytes of ONE page across all stacked layers of this leaf:
                # pool-rows axis is 1 under a scan stack ("page1"), else 0
                rows = leaf.shape[1] if ax == "page1" else leaf.shape[0]
                per_page += nbytes // rows
        if self.kv_layout == "paged":
            return per_page * self.num_pages, per_page  # trash row excluded
        return total, None

    def _bucket(self, n: int) -> int:
        b = self.prefill_bucket
        while b < n:
            b *= 2
        return min(b, self.max_len)

    def _gbucket(self, k: int) -> int:
        """Admission-group size bucket (next power of two): bounds dense
        prefill retraces to O(log max_batch * log max_len) combinations."""
        g = 1
        while g < k:
            g *= 2
        return min(g, self.max_batch)

    def _ctx(self):
        return (matmul_backend(self.backend) if self.backend is not None
                else contextlib.nullcontext())

    def _span(self, name: str) -> "_Span":
        """Profiler annotation ``name`` around a block, its wall time added
        to ``stats[span_key(name)]`` (see SPANS in the module docstring).
        ``name`` is a constant: the trace and the stats key it by name."""
        return _Span(self.stats, name)

    def _pages_needed(self, req: Request) -> int:
        total = min(req.prompt_len + req.max_new_tokens, self.slot_cap)
        return self.pool_mgr.pages_for(total)

    def _frontend_row(self, req: Request):
        if not self.cfg.frontend:
            return None
        if req.frontend is None:
            raise ValueError(
                f"arch {self.cfg.name} needs a per-request cross-attention "
                f"`frontend`, missing on: [{req.rid!r}]")
        return jnp.asarray(req.frontend, jnp.bfloat16)

    def _route(self, req: Request) -> Optional[str]:
        """The plan variant serving ``req``: the speculative target (all
        slots), the request's routed SLO class, or the backend default."""
        if self._spec is not None:
            return self._spec[1]
        if self.slo_routes and req.slo is not None:
            return self.slo_routes[req.slo]
        return None

    def _meta(self, req: Request) -> dict:
        """Per-request serving metadata, created at FIRST admission.

        ``variant``/``degraded`` are pinned here and reused on every
        resume — a request's KV numerics must stay under one variant for
        its whole lifetime.  ``tokens``/``t_first`` hold the committed
        state a preempted/faulted request resumes from; ``t_admit`` is
        when it first took a slot (its queue wait ends there)."""
        meta = self._req_meta.get(id(req))
        if meta is None:
            degraded = self._degrade is not None and self._degrade.active
            meta = {"variant": (self.degrade_to if degraded
                                else self._route(req)),
                    "degraded": degraded, "tokens": [], "t_first": None,
                    "t_admit": None, "preemptions": 0, "requeues": 0}
            self._req_meta[id(req)] = meta
        return meta

    def _eff_prompt(self, req: Request) -> np.ndarray:
        """The token stream to prefill: the original prompt, plus — for a
        request resuming after preemption/fault-requeue — every committed
        token.  Prefilling that stream reproduces the preempted slot's
        decode state exactly (the logits at its last position equal the
        decode-step logits the slot would have produced next)."""
        meta = self._req_meta.get(id(req))
        if meta and meta["tokens"]:
            return np.concatenate(
                [req.prompt, np.asarray(meta["tokens"], np.int32)])
        return req.prompt

    def _next_key(self) -> np.ndarray:
        """Per-request PRNG key row (zeros when the engine is greedy)."""
        if self.sampling is None:
            return np.zeros(2, np.uint32)
        key = request_key(self._base_key, self._req_counter)
        self._req_counter += 1
        return np.asarray(key, np.uint32)

    def _validate(self, requests: Sequence[Request]):
        for r in requests:
            if self.slo_routes and r.slo is not None \
                    and r.slo not in self.slo_routes:
                raise ValueError(
                    f"request {r.rid!r}: SLO class {r.slo!r} has no route "
                    f"(routes cover {sorted(self.slo_routes)})")
            if self.kv_layout == "dense":
                if r.prompt_len >= self.max_len:
                    raise ValueError(
                        f"request {r.rid!r}: prompt_len {r.prompt_len} does "
                        f"not fit the engine's max_len {self.max_len} "
                        f"(needs prompt_len < max_len)")
                continue
            need = self._pages_needed(r)
            if r.prompt_len >= self.slot_cap or need > self.num_pages:
                warnings.warn(
                    f"unservable request {r.rid!r}: needs {need} pages "
                    f"({r.prompt_len} prompt + {r.max_new_tokens} new tokens "
                    f"@ page_size {self.page_size}) but the pool caps at "
                    f"{self.num_pages} pages x {self.page_size} tokens "
                    f"(slot capacity {self.slot_cap})")
                raise ValueError(
                    f"request {r.rid!r}: needs {need} pages, pool has "
                    f"{self.num_pages} (slot capacity {self.slot_cap} "
                    f"tokens)")

    # ---- retirement (host-side, vectorized) ------------------------------

    def _retire_slot(self, batch: BatchState, slot: int, reason: str,
                     now: float, step: int,
                     results: Dict[int, "EngineResult"]):
        st = batch.retire(slot)
        req = st.request
        if self.kv_layout == "paged":
            self.pool_mgr.release(batch.slot_pages[slot])
            batch.slot_pages[slot] = []
            batch.page_table[slot, :] = 0
        meta = self._req_meta.get(id(req), {})
        results[id(req)] = RequestResult(
            rid=req.rid, prompt_len=req.prompt_len, tokens=st.tokens,
            finish_reason=reason, ttft_s=st.t_first - st.t_ready,
            finish_s=now - st.t_ready, admitted_step=st.admitted_step,
            finished_step=step, slo=req.slo,
            queue_s=meta.get("t_admit", st.t_ready) - st.t_ready,
            variant=meta.get("variant"),
            degraded=bool(meta.get("degraded", False)),
            preemptions=int(meta.get("preemptions", 0)),
            requeues=int(meta.get("requeues", 0)))

    def _slot_reason(self, batch: BatchState, slot: int) -> Optional[str]:
        st = batch.slots[slot]
        req = st.request
        if req.eos_id is not None and st.tokens[-1] == req.eos_id:
            return "eos"
        if len(st.tokens) >= req.max_new_tokens:
            return "max_new_tokens"
        if int(batch.lengths[slot]) >= self.slot_cap:
            return "length_cap"   # no room to embed the next token
        return None

    def _maybe_retire(self, batch: BatchState, slot: int, now: float,
                      step: int, results: Dict[int, RequestResult]) -> bool:
        reason = self._slot_reason(batch, slot)
        if reason is None:
            return False
        self._retire_slot(batch, slot, reason, now, step, results)
        return True

    def _postdecode(self, batch: BatchState, tok: np.ndarray, now: float,
                    step: int, results: Dict[int, "EngineResult"],
                    exclude: Optional[np.ndarray] = None):
        """Record one decode step's tokens and retire finished slots — one
        host sync happened already (``tok``); every predicate below reads
        host-side numpy mirrors, no per-slot device pulls.  ``exclude``
        masks slots that must NOT commit this step (stuck or faulted:
        their sampled token is garbage or missing)."""
        act = batch.active
        if exclude is not None:
            act = act & ~exclude
        idx = np.nonzero(act)[0]
        if self._monitor is not None:
            for b in idx:               # a commit is a liveness beat
                self._monitor.beat(int(b))
        batch.last_tok[idx] = tok[idx]
        batch.lengths[idx] += 1
        batch.n_gen[idx] += 1
        eos_hit = act & (batch.eos_id >= 0) & (tok == batch.eos_id)
        budget = act & (batch.n_gen >= batch.max_new)
        cap = act & (batch.lengths >= self.slot_cap)
        for b in idx:
            batch.slots[b].tokens.append(int(tok[b]))
        for b in np.nonzero(eos_hit | budget | cap)[0]:
            reason = ("eos" if eos_hit[b] else
                      "max_new_tokens" if budget[b] else "length_cap")
            self._retire_slot(batch, int(b), reason, now, step, results)

    # ---- dense admission -------------------------------------------------

    def _admit_dense(self, batch: BatchState, admits, step: int,
                     t_ready: Dict[int, float]):
        """Prefill an admission group in one call (the dense path's
        ``engine.chunk``) and assign its slots."""
        reqs = [r for _, r in admits]
        with self._span("engine.admit"):
            slots = np.asarray([s for s, _ in admits], np.int32)
            k = len(reqs)
            kp = self._gbucket(k)                 # pad the GROUP SIZE too
            P = self._bucket(max(r.prompt_len for r in reqs))
            prompts = np.zeros((kp, P), np.int32)
            lengths = np.zeros(kp, np.int32)
            keys = np.zeros((kp, 2), np.uint32)
            for i, r in enumerate(reqs):
                prompts[i, :r.prompt_len] = r.prompt
                lengths[i] = r.prompt_len
                keys[i] = self._next_key()
            # pad rows repeat the last real request (identical rows compute
            # identical caches, so the duplicate scatter writes are no-ops)
            prompts[k:] = prompts[k - 1]
            lengths[k:] = lengths[k - 1]
            slots_p = np.concatenate([slots, np.full(kp - k, slots[-1],
                                                     np.int32)])
            frontend = None
            if self.cfg.frontend:
                rows = [self._frontend_row(r) for r in reqs]
                frontend = jnp.stack(rows + [rows[-1]] * (kp - k))
        with self._span("engine.chunk"):
            t0 = time.monotonic()
            for r in reqs:
                self._meta(r)["t_admit"] = t0
            with self._span("engine.chunk.dispatch"):
                tok0, keys_out, batch.caches = self._prefill(
                    self.params, prompts, lengths, batch.caches, slots_p,
                    frontend, keys)
            with self._span("engine.chunk.wait"):
                tok0 = np.asarray(tok0)       # sync: first tokens
                if self.sampling is not None:
                    keys_out = np.asarray(keys_out)
            t1 = time.monotonic()
            self.stats["prefill_s"] += t1 - t0
            self.stats["prefill_calls"] += 1
            for i, (slot, req) in enumerate(admits):
                batch.assign(slot, req, int(tok0[i]),
                             t_ready=t_ready[id(req)], t_first=t1, step=step)
                if self.sampling is not None:
                    batch.rng[slot] = keys_out[i]
        return [s for s, _ in admits]

    # ---- paged admission + chunked prefill -------------------------------

    def _admit_paged(self, batch: BatchState, admits, step: int,
                     t_ready: Dict[int, float]):
        cow_pairs = []
        slots = []
        now = time.monotonic()
        for slot, req in admits:
            meta = self._meta(req)
            if meta["t_admit"] is None:
                meta["t_admit"] = now
            prompt = self._eff_prompt(req)
            if meta["tokens"]:
                self.stats["resumes"] += 1
            need = self._pages_needed(req)
            hit_len, shared, cow_src = (
                self.pool_mgr.match(prompt) if self.prefix_cache
                else (0, [], None))
            pages = shared + self.pool_mgr.alloc(need - len(shared))
            if cow_src is not None:
                cow_pairs.append((cow_src, pages[len(shared)]))
            batch.start_prefill(slot, req, pages, hit_len,
                                t_ready=t_ready[id(req)], step=step,
                                prompt=prompt,
                                prior_tokens=meta["tokens"],
                                t_first=meta["t_first"])
            batch.variant[slot] = meta["variant"]
            batch.rng[slot] = self._next_key()
            if self.cfg.frontend:
                row = self._frontend_row(req)
                if self._fe_buf is None:
                    self._fe_buf = jnp.zeros(
                        (self.max_batch, *row.shape), jnp.bfloat16)
                self._fe_buf = self._fe_buf.at[slot].set(row)
            slots.append(slot)
        batch.caches = self._reset(batch.caches,
                                   np.asarray(slots, np.int32))
        if cow_pairs:
            src = np.asarray([s for s, _ in cow_pairs], np.int32)
            dst = np.asarray([d for _, d in cow_pairs], np.int32)
            batch.caches = self._copy_pages(batch.caches, src, dst)
            for s, _ in cow_pairs:
                self.pool_mgr.release_cow(s)

    def _register_prompt(self, batch: BatchState, slot: int):
        """Publish a fully prefilled prompt's pages for prefix sharing.
        Uses the EFFECTIVE prompt (resumes include committed tokens —
        exact content keys, so the entries are as valid as any other)."""
        if not self.prefix_cache:
            return
        prompt = batch.pending[slot].prompt
        pages = batch.slot_pages[slot]
        for key, end in self.pool_mgr.prompt_keys(prompt):
            self.pool_mgr.register(pages[(end - 1) // self.page_size], key)

    def _variant_groups(self, batch: BatchState, sel: np.ndarray):
        """``[(variant, [slots...]), ...]`` grouping ``sel`` by per-slot
        plan variant (deterministic order: default group first)."""
        groups: Dict[Optional[str], List[int]] = {}
        for b in sel:
            groups.setdefault(batch.variant[b], []).append(int(b))
        return sorted(groups.items(),
                      key=lambda kv: (kv[0] is not None, kv[0] or ""))

    def _chunk_step(self, batch: BatchState, step: int,
                    results: Dict[int, "EngineResult"],
                    queue: Optional[RequestQueue] = None,
                    t_ready: Optional[Dict[int, float]] = None):
        """Stream the next ``prefill_chunk`` tokens of EVERY prefilling
        slot in one fixed-shape jitted call per plan-variant group (one
        call total when nothing is routed); slots whose prompt completes
        get their first token from this chunk's logits and join decode.
        Completing slots whose logits fail the isfinite screen go through
        fault containment instead of assignment."""
        B, C = self.max_batch, self.prefill_chunk
        sel = np.nonzero(batch.prefilling)[0]
        tokens = np.zeros((B, C), np.int32)
        valid_all = np.zeros(B, np.int32)
        for b in sel:
            pend = batch.pending[b]
            plen = len(pend.prompt)
            pos = int(batch.fill_pos[b])
            n = min(C, plen - pos)
            tokens[b, :n] = pend.prompt[pos:pos + n]
            valid_all[b] = n
        t0 = time.monotonic()
        outs = []
        with self._span("engine.chunk.dispatch"):
            for var, group in self._variant_groups(batch, sel):
                valid = np.zeros(B, np.int32)
                valid[group] = valid_all[group]
                tok, keys, ok, batch.caches = self._chunk(
                    self.params, tokens, batch.caches, batch.fill_pos.copy(),
                    valid, batch.page_table.copy(), self._fe_buf, batch.rng,
                    variant=var)
                outs.append((group, tok, keys, ok))
                self.stats["prefill_calls"] += 1
        tok_all = np.zeros(B, np.int32)
        ok_all = np.ones(B, bool)
        keys_all = None
        with self._span("engine.chunk.wait"):
            for group, tok, keys, ok in outs:
                tok_all[group] = np.asarray(tok)[group]     # sync
                ok_all[group] = np.asarray(ok)[group]
                if self.sampling is not None:
                    if keys_all is None:
                        keys_all = np.zeros((B, 2), np.uint32)
                    keys_all[group] = np.asarray(keys)[group]
        t1 = time.monotonic()
        self.stats["prefill_s"] += t1 - t0
        batch.fill_pos[sel] += valid_all[sel]
        batch.lengths[sel] = batch.fill_pos[sel]
        if self._monitor is not None:
            for b in sel:               # chunk progress is a liveness beat
                self._monitor.beat(int(b))
        for b in sel:
            pend = batch.pending[b]
            if batch.fill_pos[b] >= len(pend.prompt):
                if not ok_all[b] and queue is not None:
                    self._handle_fault(batch, queue, int(b), step, t1,
                                       t_ready or {}, results, purge=True)
                    continue
                self._register_prompt(batch, b)
                tf = pend.t_first if pend.t_first is not None else t1
                st = batch.assign(b, pend.request, int(tok_all[b]),
                                  t_ready=pend.t_ready, t_first=tf,
                                  step=pend.admitted_step,
                                  prompt_len=len(pend.prompt),
                                  prior_tokens=pend.prior_tokens)
                meta = self._req_meta.get(id(pend.request))
                if meta is not None and meta["t_first"] is None:
                    meta["t_first"] = tf
                    if self._degrade is not None:
                        self._degrade.observe(tf - pend.t_ready)
                if self.sampling is not None:
                    # only completing slots consumed their sample; mid-
                    # prompt slots keep their key untouched
                    batch.rng[b] = keys_all[b]
                self._maybe_retire(batch, int(b), t1, step, results)

    # ---- decode: per-variant groups --------------------------------------

    def _decode_groups(self, batch: BatchState, step: int,
                       results: Dict[int, "EngineResult"],
                       queue: Optional[RequestQueue] = None,
                       t_ready: Optional[Dict[int, float]] = None):
        """One decode step: a single jitted call per active plan-variant
        group (exactly one call when nothing is routed), the other groups'
        slots masked inactive — their paged KV writes land in the trash
        page, so groups cannot corrupt each other.  Stuck slots (injected
        liveness faults) are masked out entirely and commit nothing; slots
        failing the isfinite screen commit nothing and go through fault
        containment."""
        t = time.monotonic()
        stuck = np.zeros(self.max_batch, bool)
        for b, until in self._stuck.items():
            if until > step and batch.active[b]:
                stuck[b] = True
        inject = np.zeros(self.max_batch, np.float32)
        inject[self._inject_slots] = np.nan
        self._inject_slots = []
        outs = []
        with self._span("engine.decode.dispatch"):
            for var, group in self._variant_groups(
                    batch, np.nonzero(batch.active & ~stuck)[0]):
                mask = np.zeros(self.max_batch, bool)
                mask[group] = True
                tok, keys, ok, batch.caches = self._decode_paged(
                    self.params, batch.last_tok, batch.caches, batch.lengths,
                    mask, batch.page_table.copy(), batch.rng, inject,
                    variant=var)
                outs.append((group, tok, keys, ok))
                self.stats["decode_rows"] += len(group)
        tok_all = batch.last_tok.copy()
        ok_all = np.ones(self.max_batch, bool)
        with self._span("engine.decode.wait"):
            for group, tok, keys, ok in outs:
                tok_all[group] = np.asarray(tok)[group]     # sync
                ok_all[group] = np.asarray(ok)[group]
                if self.sampling is not None:
                    batch.rng[group] = np.asarray(keys)[group]
        now = time.monotonic()
        self.stats["decode_s"] += now - t
        self.stats["decode_steps"] += 1
        with self._span("engine.decode.commit"):
            faulted = batch.active & ~ok_all & ~stuck
            self._postdecode(batch, tok_all, now, step, results,
                             exclude=(stuck | faulted))
            if queue is not None:
                for b in np.nonzero(faulted)[0]:
                    if batch.active[b]:     # not retired by _postdecode
                        self._handle_fault(batch, queue, int(b), step, now,
                                           t_ready or {}, results,
                                           purge=True)

    # ---- self-speculative decoding ---------------------------------------

    def _spec_round(self, batch: BatchState, step: int,
                    results: Dict[int, RequestResult]):
        """One speculative round: draft ``k`` tokens per active slot with
        the draft variant, verify all of them in one target-variant chunk,
        commit the longest agreeing prefix plus the bonus target token
        (applying the per-token retire predicates exactly as sequential
        decoding would), and replay partially-accepting slots' recurrent
        state when the arch has any."""
        k = self.draft_k
        sel = np.nonzero(batch.active)[0]
        tok0 = batch.last_tok.copy()
        fill0 = batch.lengths.copy()
        snap = batch.caches                  # pre-draft arrays (immutable)
        t = time.monotonic()
        with self._span("engine.decode.dispatch"):
            drafted, batch.caches = self._draft(
                self.params, tok0, batch.caches, fill0, batch.active.copy(),
                batch.page_table.copy())
            vcount = np.zeros(self.max_batch, np.int32)
            vcount[sel] = np.minimum(k + 1, self.slot_cap - fill0[sel])
            vtok, batch.caches = self._verify(
                self.params, tok0, drafted, batch.caches, snap, fill0,
                vcount, batch.page_table.copy())
        self.stats["decode_rows"] += len(sel)
        with self._span("engine.decode.wait"):
            d = np.asarray(drafted)          # sync (both calls dispatched)
            v = np.asarray(vtok)
        now = time.monotonic()
        self.stats["decode_s"] += now - t
        self.stats["decode_steps"] += 1
        self.stats["spec_rounds"] += 1
        replay_valid = np.zeros(self.max_batch, np.int32)
        with self._span("engine.decode.commit"):
            for b in sel:
                vc = int(vcount[b])
                # drafts that could actually commit: the slot's remaining
                # token budget caps the round, so over-drafting past it is
                # not a draft-quality failure and must not dilute the
                # acceptance rate
                budget_left = int(batch.max_new[b] - batch.n_gen[b])
                m = 0                        # agreeing draft prefix
                while m < vc - 1 and d[b, m] == v[b, m]:
                    m += 1
                st = batch.slots[b]
                committed = 0
                retired = False
                for j in range(m + 1):       # m matches + 1 bonus token
                    tokj = int(v[b, j])
                    st.tokens.append(tokj)
                    batch.last_tok[b] = tokj
                    batch.lengths[b] += 1
                    batch.n_gen[b] += 1
                    committed += 1
                    reason = self._slot_reason(batch, int(b))
                    if reason is not None:
                        self._retire_slot(batch, int(b), reason, now, step,
                                          results)
                        retired = True
                        break
                self.stats["spec_drafted"] += min(vc - 1, budget_left)
                self.stats["spec_accepted"] += min(committed, m)
                self.stats["spec_committed"] += committed
                if not retired and committed < vc:
                    replay_valid[b] = committed
        if self._has_recurrent and replay_valid.any():
            t = time.monotonic()
            with self._span("engine.decode.dispatch"):
                batch.caches = self._replay(
                    self.params, tok0, drafted, batch.caches, snap, fill0,
                    replay_valid, batch.page_table.copy())
            self.stats["decode_s"] += time.monotonic() - t

    # ---- robustness: preemption, shedding, faults ------------------------

    def _free_slots(self, batch: BatchState, step: int) -> List[int]:
        """Free slots minus the quarantined ones."""
        return [b for b in batch.free_slots()
                if self._quarantine.get(b, 0) <= step]

    def _maybe_preempt(self, batch: BatchState, queue: RequestQueue,
                       t_ready: Dict[int, float], step: int, now: float):
        """Retire-and-requeue at most ONE active slot when a visible
        queued request is strictly more urgent than the least-urgent
        running one and cannot be served from free capacity.  The victim's
        committed tokens are recorded for resumption; with the prefix
        cache on, its filled pages are registered under the resume
        prompt's keys first, so they park in the LRU and resumption
        re-prefills only the unhashed tail."""
        waiting = [r for r in queue if r.arrival_step <= step]
        if not waiting:
            return
        front = min(waiting, key=lambda r: urgency(r, now,
                                                   t_ready.get(id(r))))
        if self._free_slots(batch, step) and \
                self._pages_needed(front) <= self.pool_mgr.available():
            return          # plain admission can serve it this step
        cands = [b for b in range(self.max_batch)
                 if batch.active[b] and not self._stuck.get(b, 0) > step
                 and self._req_meta[id(batch.slots[b].request)]
                 ["preemptions"] < self.max_preemptions]
        if not cands:
            return
        victim = max(cands, key=lambda b: urgency(
            batch.slots[b].request, now,
            t_ready.get(id(batch.slots[b].request))))
        vreq = batch.slots[victim].request
        if not urgency(front, now, t_ready.get(id(front))) < \
                urgency(vreq, now, t_ready.get(id(vreq))):
            return          # nobody waiting beats the least-urgent runner
        st = batch.retire(victim)
        meta = self._req_meta[id(vreq)]
        meta["tokens"] = list(st.tokens)
        meta["t_first"] = st.t_first
        meta["preemptions"] += 1
        pages = batch.slot_pages[victim]
        if self.prefix_cache:
            # the cache holds resume_prompt[:filled] (the last committed
            # token is not in the cache yet) — publish exactly that, so
            # the resume prefill prefix-matches everything but the tail
            filled = int(batch.lengths[victim])
            resume = self._eff_prompt(vreq)
            for key, end in self.pool_mgr.prompt_keys(resume[:filled]):
                self.pool_mgr.register(pages[(end - 1) // self.page_size],
                                       key)
        self.pool_mgr.release(pages)
        batch.slot_pages[victim] = []
        batch.page_table[victim, :] = 0
        queue.push_front(vreq)
        self.stats["preemptions"] += 1

    def _shed(self, req: Request, reason: str, step: int, waited: float,
              results: Dict[int, "EngineResult"]):
        results[id(req)] = ShedResult(rid=req.rid, reason=reason,
                                      shed_step=step,
                                      waited_s=round(max(waited, 0.0), 6),
                                      slo=req.slo)
        self.stats["shed_requests"] += 1

    def _resumable(self, req: Request) -> bool:
        """Requests holding committed tokens (preempted/faulted, waiting
        to resume) are never backlog-shed — that would discard served
        work.  The wall-clock timeout still applies to them."""
        return bool(self._req_meta.get(id(req), {}).get("tokens"))

    def _timeout_queued(self, queue: RequestQueue,
                        t_ready: Dict[int, float], step: int, now: float,
                        results: Dict[int, "EngineResult"]):
        """Shed visible queued requests that outlived the wall-clock
        budget (run BEFORE admission: a timed-out request is dead even if
        a slot just freed — the client stopped waiting)."""
        if self.request_timeout_s is None:
            return
        for r in [r for r in queue if r.arrival_step <= step]:
            waited = now - t_ready.get(id(r), now)
            if waited > self.request_timeout_s:
                queue.remove(r)
                self.stats["timeouts"] += 1
                self._shed(r, "timeout", step, waited, results)

    def _shed_backlog(self, queue: RequestQueue,
                      t_ready: Dict[int, float], step: int, now: float,
                      results: Dict[int, "EngineResult"],
                      free_frac: Optional[float] = None):
        """Bound the POST-admission backlog (run after the step's
        admissions): overflow beyond ``max_queue_depth`` sheds newest
        visible first; below the free-page watermark everything behind
        the head of line sheds (the head keeps its place — head-of-line
        blocking already guarantees it admits as soon as pages free)."""
        if self.max_queue_depth is not None:
            visible = [r for r in queue if r.arrival_step <= step]
            excess = len(visible) - self.max_queue_depth
            for r in reversed(visible):
                if excess <= 0:
                    break
                if self._resumable(r):
                    continue
                queue.remove(r)
                excess -= 1
                self._shed(r, "queue_depth", step,
                           now - t_ready.get(id(r), now), results)
        if self.page_watermark is not None and free_frac is not None \
                and free_frac < self.page_watermark:
            for r in [r for r in queue if r.arrival_step <= step][1:]:
                if self._resumable(r):
                    continue
                queue.remove(r)
                self._shed(r, "page_watermark", step,
                           now - t_ready.get(id(r), now), results)

    def _timeout_running(self, batch: BatchState, step: int, now: float,
                         results: Dict[int, "EngineResult"]):
        """Retire ACTIVE slots whose request outlived the wall-clock
        budget — they keep their partial tokens, ``finish_reason=
        "timeout"``.  (Prefilling slots complete their bounded prefill
        first and time out on the next sweep.)"""
        if self.request_timeout_s is None:
            return
        for b in range(self.max_batch):
            if batch.active[b] and \
                    now - batch.slots[b].t_ready > self.request_timeout_s:
                self.stats["timeouts"] += 1
                self._retire_slot(batch, b, "timeout", now, step, results)

    def _apply_faults(self, batch: BatchState, step: int):
        """Draw this step's injected faults and arm them: NaN slots for
        the decode inject vector, NaN-stomped KV pages, stuck markers."""
        if self.injector is None:
            return
        occupied = [b for b in range(self.max_batch)
                    if batch.active[b] or batch.prefilling[b]]
        if not occupied:
            return
        for ev in self.injector.draw(step, occupied):
            self.stats["faults_injected"] += 1
            if ev.kind == "nonfinite_logits":
                self._inject_slots.append(ev.slot)
            elif ev.kind == "corrupt_page":
                # corrupt the page holding the slot's newest WRITTEN
                # position — guaranteed inside the attention window, so
                # detection on the next step is certain
                filled = max(int(batch.lengths[ev.slot]), 1)
                pages = batch.slot_pages[ev.slot]
                page = pages[min((filled - 1) // self.page_size,
                                 len(pages) - 1)]
                batch.caches = self._corrupt_pages(
                    batch.caches, np.asarray([page], np.int32))
            elif ev.kind == "stuck":
                self._stuck[ev.slot] = step + ev.duration

    def _handle_fault(self, batch: BatchState, queue: RequestQueue,
                      slot: int, step: int, now: float,
                      t_ready: Dict[int, float],
                      results: Dict[int, "EngineResult"], *,
                      purge: bool, kind: str = "numeric"):
        """Contain a detected fault on ``slot``: release (and for numeric
        faults PURGE — corrupted content must never be prefix-matched)
        its pages, quarantine the slot, and requeue the request ONCE with
        its committed tokens; a second fault sheds it with
        ``ShedResult(reason="fault")``."""
        self.stats["faults_detected"] += 1
        if batch.active[slot]:
            st = batch.retire(slot)
            req, tokens, tf = st.request, list(st.tokens), st.t_first
        else:
            pend = batch.pending[slot]
            req, tokens, tf = (pend.request, list(pend.prior_tokens),
                               pend.t_first)
            batch.prefilling[slot] = False
            batch.pending[slot] = None
            batch.fill_pos[slot] = 0
        pages = batch.slot_pages[slot]
        if purge:
            self.pool_mgr.purge(pages)
        self.pool_mgr.release(pages)
        batch.slot_pages[slot] = []
        batch.page_table[slot, :] = 0
        self._quarantine[slot] = step + self.quarantine_steps
        self._stuck.pop(slot, None)
        meta = self._req_meta[id(req)]
        if meta["requeues"] >= 1:       # requeue-once policy
            self._shed(req, "fault", step,
                       now - t_ready.get(id(req), now), results)
            return
        meta["requeues"] += 1
        meta["tokens"] = tokens         # committed tokens predate the
        meta["t_first"] = tf            # fault: clean, resume from them
        queue.push_front(req)

    # ---- main loops ------------------------------------------------------

    def run(self, requests: Sequence[Request]) -> List["EngineResult"]:
        """Serve ``requests`` to completion; returns one result per
        request, in submission order — a `RequestResult` for requests that
        finished, a `ShedResult` for requests the overload/fault paths
        rejected.  Timing aggregates land in ``self.stats``."""
        self._validate(requests)
        self.stats = {"prefill_s": 0.0, "decode_s": 0.0, "decode_steps": 0,
                      "prefill_calls": 0, "wall_s": 0.0,
                      "preemptions": 0, "resumes": 0, "shed_requests": 0,
                      "timeouts": 0, "faults_injected": 0,
                      "faults_detected": 0, "degrade_transitions": 0,
                      "heartbeat_trips": 0, "decode_rows": 0}
        if self._spec is not None:
            self.stats.update({"spec_rounds": 0, "spec_drafted": 0,
                               "spec_accepted": 0, "spec_committed": 0})
        self._req_counter = 0
        self._req_meta = {}
        self._quarantine = {}
        self._stuck = {}
        self._inject_slots = []
        if self._degrade is not None:
            self._degrade.reset()
        queue = RequestQueue()
        for r in requests:
            queue.push(r)
        results: Dict[int, EngineResult] = {}
        t0 = time.monotonic()
        with self._span("engine.run"):
            if self.kv_layout == "paged":
                self._run_paged(queue, results)
            else:
                self._run_dense(queue, results)
        self.stats["wall_s"] = time.monotonic() - t0
        self.stats["kv_capacity_bytes"] = self._kv_capacity_bytes
        if self._spec is not None:
            drafted = self.stats["spec_drafted"]
            self.stats["spec_acceptance"] = (
                round(self.stats["spec_accepted"] / drafted, 4)
                if drafted else 0.0)
            rounds = self.stats["spec_rounds"]
            self.stats["spec_tokens_per_round"] = (
                round(self.stats["spec_committed"] / rounds, 4)
                if rounds else 0.0)
        if self.kv_layout == "paged":
            ps = self.pool_mgr.stats
            self.stats["kv_peak_pages"] = ps["peak_pages"]
            self.stats["kv_page_bytes"] = self._kv_page_bytes
            self.stats["kv_peak_bytes"] = ps["peak_pages"] * \
                self._kv_page_bytes
            self.stats["prefix_lookups"] = ps["lookups"]
            self.stats["prefix_hit_requests"] = ps["hit_requests"]
            self.stats["prefix_hit_tokens"] = ps["hit_tokens"]
            self.stats["cow_copies"] = ps["cow_copies"]
            self.stats["page_evictions"] = ps["evictions"]
        else:
            # dense pools are fully allocated up front: peak == capacity
            self.stats["kv_peak_bytes"] = self._kv_capacity_bytes
        if self._degrade is not None:
            self.stats["degrade_transitions"] = \
                len(self._degrade.transitions)
        return [results[id(r)] for r in requests]

    def _run_dense(self, queue: RequestQueue,
                   results: Dict[int, "EngineResult"]):
        batch = BatchState(self.max_batch,
                           T.init_cache(self.cfg, self.max_batch,
                                        self.max_len))
        t_ready: Dict[int, float] = {}
        step = 0
        with self._ctx():
            while len(queue) or batch.any_active():
                with self._span("engine.step"):
                    with self._span("engine.schedule"):
                        # idle + only future arrivals: fast-forward the
                        # step clock
                        if not batch.any_active() and \
                                queue.ready(step) == 0:
                            step = max(step, queue.next_arrival())
                        now = time.monotonic()
                        for r in queue:
                            if r.arrival_step <= step and \
                                    id(r) not in t_ready:
                                t_ready[id(r)] = now
                        self._timeout_queued(queue, t_ready, step, now,
                                             results)
                        self._timeout_running(batch, step, now, results)
                        admits = self.scheduler.admissions(
                            queue, batch.free_slots(), batch.n_active, step,
                            now=now, t_ready=t_ready)
                        for _, req in admits:
                            self._meta(req)     # pin variant/degraded
                        self._shed_backlog(queue, t_ready, step, now,
                                           results)
                    if admits:
                        for slot in self._admit_dense(batch, admits, step,
                                                      t_ready):
                            self._maybe_retire(batch, slot,
                                               time.monotonic(), step,
                                               results)
                    if not batch.any_active():
                        continue
                    with self._span("engine.decode"):
                        self._dense_decode(batch, step, results)
                    step += 1

    def _dense_decode(self, batch: BatchState, step: int,
                      results: Dict[int, "EngineResult"]):
        """One decode step of every active dense slot."""
        t = time.monotonic()
        with self._span("engine.decode.dispatch"):
            tok, keys, batch.caches = self._decode(
                self.params, batch.last_tok, batch.caches, batch.lengths,
                batch.active, batch.rng)
        self.stats["decode_rows"] += batch.n_active
        with self._span("engine.decode.wait"):
            tok = np.asarray(tok)                   # sync
            act = np.nonzero(batch.active)[0]
            if self.sampling is not None:
                batch.rng[act] = np.asarray(keys)[act]
        now = time.monotonic()
        self.stats["decode_s"] += now - t
        self.stats["decode_steps"] += 1
        with self._span("engine.decode.commit"):
            self._postdecode(batch, tok, now, step, results)

    def _run_paged(self, queue: RequestQueue,
                   results: Dict[int, "EngineResult"]):
        if self._paged_caches is None:
            rows = self.num_pages + 1                  # + trash page 0
            self._paged_caches = T.init_paged_cache(
                self.cfg, self.max_batch, rows, self.page_size)
        batch = BatchState(self.max_batch, self._paged_caches,
                           pages_per_slot=self.pages_per_slot)
        self._fe_buf = None
        t_ready: Dict[int, float] = {}
        step = 0
        # the liveness monitor runs on the STEP clock (host keys are slot
        # ids): a slot that commits nothing / makes no prefill progress
        # for heartbeat_steps steps is declared stuck
        step_ref = [0]
        self._monitor = HeartbeatMonitor(
            hosts=list(range(self.max_batch)),
            deadline_s=float(self.heartbeat_steps),
            clock=lambda: float(step_ref[0]))
        with self._ctx():
            while len(queue) or batch.any_busy():
                with self._span("engine.step"):
                    with self._span("engine.schedule"):
                        if not batch.any_busy() and queue.ready(step) == 0:
                            step = max(step, queue.next_arrival())
                        step_ref[0] = step
                        now = time.monotonic()
                        for r in queue:
                            if r.arrival_step <= step and \
                                    id(r) not in t_ready:
                                t_ready[id(r)] = now
                        self._timeout_queued(queue, t_ready, step, now,
                                             results)
                        self._timeout_running(batch, step, now, results)
                        if self.scheduler.preempts:
                            self._maybe_preempt(batch, queue, t_ready, step,
                                                now)
                        reserved = [0]

                        def fits(req):
                            # running reservation: one admission round may
                            # pop several requests before any pages are
                            # allocated
                            need = self._pages_needed(req)
                            if reserved[0] + need <= \
                                    self.pool_mgr.available():
                                reserved[0] += need
                                return True
                            return False

                        admits = self.scheduler.admissions(
                            queue, self._free_slots(batch, step),
                            batch.n_busy, step, fits=fits, now=now,
                            t_ready=t_ready)
                        for _, req in admits:
                            self._meta(req)     # pin variant/degraded
                    if admits:
                        with self._span("engine.admit"):
                            self._admit_paged(batch, admits, step, t_ready)
                    with self._span("engine.schedule"):
                        self._shed_backlog(
                            queue, t_ready, step, now, results,
                            free_frac=(self.pool_mgr.available()
                                       / self.num_pages))
                        self._apply_faults(batch, step)
                    if batch.prefilling.any():
                        with self._span("engine.chunk"):
                            self._chunk_step(batch, step, results,
                                             queue=queue, t_ready=t_ready)
                    if batch.any_active():
                        with self._span("engine.decode"):
                            if self._spec is not None:
                                self._spec_round(batch, step, results)
                            else:
                                self._decode_groups(batch, step, results,
                                                    queue=queue,
                                                    t_ready=t_ready)
                    # idle slots are not stuck: keep their heartbeats fresh
                    for b in range(self.max_batch):
                        if not (batch.active[b] or batch.prefilling[b]):
                            self._monitor.beat(b)
                    for b in self._monitor.dead_hosts():
                        if batch.active[b] or batch.prefilling[b]:
                            self.stats["heartbeat_trips"] += 1
                            self._handle_fault(batch, queue, int(b), step,
                                               time.monotonic(), t_ready,
                                               results, purge=False,
                                               kind="stuck")
                        self._monitor.beat(b)
                    if self._degrade is not None:
                        self._degrade.update(step)   # _meta reads .active
                step += 1
        self._monitor = None
        self._paged_caches = batch.caches       # keep cached pages resident


class _DegradeController:
    """Hysteresis switch for graceful precision degradation.

    Observes TTFTs as requests get their first token; `update` (once per
    engine step) flips ``active`` ON when the sliding-window p95 breaches
    the target, and OFF once p95 drops below ``recover_frac * target``.
    The window is cleared at each transition so pre-transition samples
    cannot immediately flip it back, and a minimum sample count must
    accumulate again before the next decision — that is the hysteresis.
    Transitions are recorded as ``(step, "degrade"|"recover", p95_s)``."""

    def __init__(self, target_s: float, window: int = 8,
                 min_samples: int = 4, recover_frac: float = 0.7):
        if target_s <= 0:
            raise ValueError(f"ttft_target_s must be > 0, got {target_s}")
        if not 0.0 < recover_frac <= 1.0:
            raise ValueError(f"degrade_recover_frac must be in (0, 1], "
                             f"got {recover_frac}")
        self.target_s = float(target_s)
        self.min_samples = max(1, min(int(min_samples), int(window)))
        self.recover_frac = float(recover_frac)
        self.samples: deque = deque(maxlen=int(window))
        self.active = False
        self.transitions: List[Tuple[int, str, float]] = []

    def reset(self):
        self.samples.clear()
        self.active = False
        self.transitions.clear()    # in place: Engine.degrade_log aliases

    def observe(self, ttft_s: float):
        self.samples.append(float(ttft_s))

    def update(self, step: int) -> bool:
        if len(self.samples) < self.min_samples:
            return self.active
        p95 = percentile(list(self.samples), 95)
        if not self.active and p95 > self.target_s:
            self.active = True
            self.transitions.append((step, "degrade", round(p95, 6)))
            self.samples.clear()
        elif self.active and p95 < self.recover_frac * self.target_s:
            self.active = False
            self.transitions.append((step, "recover", round(p95, 6)))
            self.samples.clear()
        return self.active
