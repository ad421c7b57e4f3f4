"""Continuous-batching serving engine tests (`repro.serving`).

Covers: scheduler admission/retirement mechanics (no model), engine-vs-
legacy-loop greedy token parity on same-length prompts (with and without a
planned mapping backend), the ISSUE acceptance criterion — engine tokens
identical to per-request `serve_batch` on a MIXED-length prompt set with a
fully covered diana plan (zero fp fallbacks) — and a masked-decode
regression pinning per-slot cache lengths against single-request decode.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base as cfgbase
from repro.models import transformer as T
from repro.models.managed import matmul_backend
from repro.serving import (BatchState, Engine, Request, RequestQueue,
                           Scheduler, load_trace, save_trace,
                           synthetic_trace)


@pytest.fixture(scope="module", autouse=True)
def _load():
    cfgbase.load_all()


def _reduced(arch):
    return cfgbase.reduce_for_smoke(cfgbase.get(arch))


def _legacy_serve_batch(cfg, params, prompts, gen_len, backend=None):
    """The pre-engine fixed-shape serve loop (scalar cache_index), kept
    verbatim as the migration parity oracle for `serve_batch`."""
    B, P = prompts.shape
    caches = T.init_cache(cfg, B, P + gen_len)
    prefill = jax.jit(lambda p, t, c: T.prefill(p, cfg, t, c))
    decode = jax.jit(lambda p, t, c, i: T.decode_step(p, cfg, t, c, i))
    ctx = (matmul_backend(backend) if backend is not None
           else contextlib.nullcontext())
    with ctx:
        logits, caches = prefill(params, prompts, caches)
        tok = jnp.argmax(logits, -1)
        out = [tok]
        for i in range(gen_len - 1):
            logits, caches = decode(params, tok, caches, P + i)
            tok = jnp.argmax(logits, -1)
            out.append(tok)
    return np.asarray(jnp.stack(out, axis=1))


def _diana_artifact(cfg, params, tmp_path, act_log_scale=2.0):
    """Static min-cost diana artifact with STATIC activation scales (the
    engine's per-request reproducibility precondition)."""
    from repro.launch.train import emit_static_mapping
    return emit_static_mapping(params, cfg, "diana",
                               tmp_path / "mapping.json",
                               act_log_scale=act_log_scale)


# --------------------------------------------------------------------------
# scheduler / queue / batch-state mechanics (no model)
# --------------------------------------------------------------------------

def _req(rid, plen=4, new=4, arrival=0):
    return Request(rid=rid, prompt=np.arange(plen) % 7, max_new_tokens=new,
                   arrival_step=arrival)


def test_queue_arrival_visibility_and_fcfs():
    q = RequestQueue()
    for r in (_req("a"), _req("b", arrival=3), _req("c")):
        q.push(r)
    assert len(q) == 3 and q.ready(0) == 2 and q.ready(3) == 3
    assert q.next_arrival() == 0
    got = q.pop_ready(0, 5)
    assert [r.rid for r in got] == ["a", "c"]     # FCFS among visible
    assert [r.rid for r in q] == ["b"]
    assert q.pop_ready(0, 5) == [] and q.next_arrival() == 3


def test_scheduler_continuous_fills_free_slots():
    q = RequestQueue()
    for i in range(3):
        q.push(_req(i))
    adm = Scheduler("continuous").admissions(q, free_slots=[0, 2],
                                             n_active=2, step=0)
    assert [(s, r.rid) for s, r in adm] == [(0, 0), (2, 1)]
    assert len(q) == 1


def test_scheduler_static_waits_for_drain():
    q = RequestQueue()
    q.push(_req("x"))
    sched = Scheduler("static")
    assert sched.admissions(q, free_slots=[1], n_active=1, step=0) == []
    assert len(q) == 1                       # nothing popped while active
    adm = sched.admissions(q, free_slots=[0, 1], n_active=0, step=0)
    assert [(s, r.rid) for s, r in adm] == [(0, "x")]


def test_scheduler_rejects_unknown_policy():
    with pytest.raises(ValueError, match="policy"):
        Scheduler("round_robin")


def test_batchstate_slot_lifecycle():
    bs = BatchState(2, caches=None)
    assert bs.free_slots() == [0, 1] and not bs.any_active()
    st = bs.assign(0, _req("a", plen=3), first_token=5, t_ready=0.0,
                   t_first=0.1, step=0)
    assert bs.active[0] and bs.lengths[0] == 3 and bs.last_tok[0] == 5
    assert st.tokens == [5] and bs.free_slots() == [1]
    with pytest.raises(RuntimeError, match="active"):
        bs.assign(0, _req("b"), 1, 0.0, 0.0, 0)
    assert bs.retire(0).request.rid == "a"
    assert bs.free_slots() == [0, 1]
    with pytest.raises(RuntimeError, match="not occupied"):
        bs.retire(0)


def test_request_validation():
    with pytest.raises(ValueError, match="empty prompt"):
        Request(rid=0, prompt=np.zeros(0), max_new_tokens=2)
    with pytest.raises(ValueError, match="max_new_tokens"):
        Request(rid=0, prompt=np.zeros(3), max_new_tokens=0)


def test_trace_roundtrip_and_determinism(tmp_path):
    t1 = synthetic_trace(5, vocab=64, seed=3, arrival_every=2)
    t2 = synthetic_trace(5, vocab=64, seed=3, arrival_every=2)
    assert all(np.array_equal(a.prompt, b.prompt) and
               a.max_new_tokens == b.max_new_tokens and
               a.arrival_step == b.arrival_step for a, b in zip(t1, t2))
    p = save_trace(tmp_path / "t.jsonl", t1)
    t3 = load_trace(p)
    assert all(np.array_equal(a.prompt, b.prompt) and a.rid == b.rid
               for a, b in zip(t1, t3))


# --------------------------------------------------------------------------
# engine vs the legacy fixed-shape loop (serve_batch migration parity)
# --------------------------------------------------------------------------

def test_serve_batch_matches_legacy_loop():
    """`serve_batch` (now an engine wrapper) is token-identical to the old
    fixed-shape prefill/decode loop on a same-length batch."""
    from repro.launch.serve import serve_batch
    cfg = _reduced("yi-9b")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (3, 8), 0, cfg.vocab)
    gen, stats = serve_batch(cfg, params, prompts, gen_len=5)
    legacy = _legacy_serve_batch(cfg, params, prompts, gen_len=5)
    np.testing.assert_array_equal(np.asarray(gen), legacy)
    assert stats["tok_per_s"] > 0


@pytest.mark.slow
def test_serve_batch_matches_legacy_loop_planned(tmp_path):
    """Same-length parity WITH the planned diana backend bound: the engine
    route and the legacy loop execute identical planned kernels."""
    from repro.launch.serve import plan_mapping_execution, serve_batch
    cfg = _reduced("zamba2-1.2b")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    art = _diana_artifact(cfg, params, tmp_path)
    plan, backend = plan_mapping_execution(params, art)
    assert "fp" not in plan.kernel_histogram()
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    gen, _ = serve_batch(cfg, params, prompts, gen_len=4, backend=backend)
    legacy = _legacy_serve_batch(cfg, params, prompts, gen_len=4,
                                 backend=backend)
    np.testing.assert_array_equal(np.asarray(gen), legacy)
    assert not backend.unbound and not backend.runtime_declines


# --------------------------------------------------------------------------
# acceptance: mixed-length engine == per-request serve_batch, planned diana
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_engine_mixed_length_per_request_parity_planned(tmp_path):
    """ISSUE acceptance criterion: on a mixed-length prompt set with the
    planned backend bound (diana, zero fp fallbacks), the continuous-
    batching engine produces token-identical greedy outputs vs per-request
    `serve_batch`."""
    from repro.launch.serve import plan_mapping_execution, serve_batch
    cfg = _reduced("zamba2-1.2b")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    art = _diana_artifact(cfg, params, tmp_path)
    plan, backend = plan_mapping_execution(params, art)
    assert "fp" not in plan.kernel_histogram(), plan.kernel_histogram()

    rng = np.random.default_rng(5)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, size=plen),
                    max_new_tokens=new)
            for i, (plen, new) in enumerate([(7, 4), (3, 5), (8, 3),
                                             (5, 4)])]
    eng = Engine(cfg, params, max_batch=2, max_len=16, backend=backend)
    results = eng.run(reqs)
    assert backend.fully_covered and not backend.runtime_declines

    for r, res in zip(reqs, results):
        gen, _ = serve_batch(cfg, params, jnp.asarray(r.prompt)[None],
                             gen_len=r.max_new_tokens, backend=backend)
        assert res.tokens == list(np.asarray(gen)[0]), \
            (r.rid, res.tokens, np.asarray(gen)[0])


def test_engine_mixed_length_per_request_parity_fp():
    """Mixed-length engine-vs-per-request parity without a mapping (pure
    bf16/f32 path), yi-9b reduced — the cheap always-on version of the
    acceptance test."""
    from repro.launch.serve import serve_batch
    cfg = _reduced("yi-9b")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(9)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=plen),
                    max_new_tokens=new)
            for i, (plen, new) in enumerate([(6, 3), (2, 6), (9, 2),
                                             (4, 4), (3, 3)])]
    eng = Engine(cfg, params, max_batch=2, max_len=16)
    results = eng.run(reqs)
    for r, res in zip(reqs, results):
        gen, _ = serve_batch(cfg, params, jnp.asarray(r.prompt)[None],
                             gen_len=r.max_new_tokens)
        assert res.tokens == list(np.asarray(gen)[0]), (r.rid,)


# --------------------------------------------------------------------------
# slot retirement / admission through the engine
# --------------------------------------------------------------------------

def test_engine_retirement_and_admission():
    """Slots retire on max_new_tokens/eos/length_cap and are refilled
    mid-flight; every request completes with the right finish reason."""
    cfg = _reduced("yi-9b")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(11)
    mk = lambda i, plen, new, **kw: Request(
        rid=i, prompt=rng.integers(0, cfg.vocab, size=plen),
        max_new_tokens=new, **kw)
    # learn a token to use as EOS for request 1: the first decoded token
    # that did not occur earlier in the stream, so EOS fires exactly there
    # (a random model may repeat one token, so draw prompts until one works)
    probe = Engine(cfg, params, max_batch=1, max_len=16)
    for _ in range(8):
        r1 = mk(1, 5, 6)
        probe_tok = probe.run([Request(rid="p", prompt=r1.prompt,
                                       max_new_tokens=6)])[0].tokens
        eos_at = next((i for i in range(1, len(probe_tok))
                       if probe_tok[i] not in probe_tok[:i]), None)
        if eos_at is not None:
            break
    assert eos_at is not None, probe_tok
    reqs = [
        mk(0, 4, 1),                                   # retires at admission
        Request(rid=1, prompt=r1.prompt, max_new_tokens=6,
                eos_id=int(probe_tok[eos_at])),        # retires on EOS
        mk(2, 14, 8),                                  # hits the length cap
        mk(3, 3, 4),                                   # fills a freed slot
        mk(4, 3, 3, arrival_step=2),                   # late arrival
    ]
    eng = Engine(cfg, params, max_batch=2, max_len=16)
    res = {r.rid: r for r in eng.run(reqs)}
    assert res[0].finish_reason == "max_new_tokens" and res[0].n_tokens == 1
    assert res[0].finished_step == res[0].admitted_step   # no decode needed
    assert res[1].finish_reason == "eos" and res[1].n_tokens == eos_at + 1
    assert res[1].tokens == list(probe_tok[:eos_at + 1])
    assert res[2].finish_reason == "length_cap"
    assert res[2].prompt_len + res[2].n_tokens - 1 == 16  # pool exhausted
    assert res[3].finish_reason == "max_new_tokens" and res[3].n_tokens == 4
    assert res[4].n_tokens == 3 and res[4].admitted_step >= 2
    assert all(r.ttft_s >= 0 and r.finish_s >= r.ttft_s
               for r in res.values())


def test_engine_rejects_oversized_prompt():
    """Dense keeps the old hard max_len bound; paged admits anything that
    fits in ``pages_per_slot * page_size`` tokens and only refuses (with a
    warning naming the request and its page requirement) beyond that."""
    cfg = _reduced("yi-9b")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, max_batch=1, max_len=8, kv_layout="dense")
    with pytest.raises(ValueError, match="max_len"):
        eng.run([Request(rid=0, prompt=np.zeros(8, np.int32),
                         max_new_tokens=2)])
    # paged: the same prompt fits (slot capacity = ceil(8/4)*4 = 8 tokens
    # of pages, prompt 8 needs all of them and decode budget spills past —
    # still admitted, generation just stops at the slot capacity)
    eng = Engine(cfg, params, max_batch=1, max_len=8, kv_layout="paged",
                 page_size=4)
    res = eng.run([Request(rid=0, prompt=np.zeros(6, np.int32),
                           max_new_tokens=2)])
    assert len(res[0].tokens) == 2
    # ... but a prompt beyond the whole slot's page capacity is unservable
    with pytest.warns(UserWarning, match="unservable request 'big'"):
        with pytest.raises(ValueError, match="pages"):
            eng.run([Request(rid="big", prompt=np.zeros(9, np.int32),
                             max_new_tokens=2)])


def test_engine_static_policy_same_tokens_more_steps():
    """The static gang-batching baseline produces the same greedy tokens but
    cannot overlap mixed-length requests (>= decode steps, ttft no
    better)."""
    cfg = _reduced("yi-9b")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    trace = synthetic_trace(6, vocab=cfg.vocab, min_prompt=3, max_prompt=10,
                            min_new=2, max_new=8, seed=2)
    cont = Engine(cfg, params, max_batch=2, max_len=20)
    res_c = cont.run(trace)
    stat = Engine(cfg, params, max_batch=2, max_len=20,
                  scheduler=Scheduler("static"))
    res_s = stat.run(trace)
    assert [r.tokens for r in res_c] == [r.tokens for r in res_s]
    assert stat.stats["decode_steps"] >= cont.stats["decode_steps"]


# --------------------------------------------------------------------------
# masked-decode regression: per-slot cache lengths
# --------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["yi-9b", "zamba2-1.2b"])
def test_masked_decode_per_slot_cache_lengths(arch):
    """Per-slot decode (index (B,), per-slot kv masking) must match scalar
    single-request decode for every slot, with slots parked at DIFFERENT
    cache lengths and garbage KV beyond each slot's length (the ragged-
    prefill contract)."""
    cfg = _reduced(arch)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    S_max, P_pad = 16, 8
    lens = [6, 8, 2]
    B = len(lens)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, size=l) for l in lens]
    padded = jnp.asarray(np.stack(
        [np.pad(p, (0, P_pad - len(p))) for p in prompts]).astype(np.int32))
    caches = T.init_cache(cfg, B, S_max)
    lengths = jnp.asarray(lens, jnp.int32)
    logits, caches = T.prefill(params, cfg, padded, caches, lengths=lengths)
    tok = jnp.argmax(logits, -1)
    seqs = [tok]
    for step in range(3):
        logits, caches = T.decode_step(params, cfg, tok, caches,
                                       lengths + step,
                                       active=jnp.ones((B,), bool))
        tok = jnp.argmax(logits, -1)
        seqs.append(tok)
    got = np.asarray(jnp.stack(seqs, axis=1))            # (B, 4)
    # reference: each slot alone, scalar index, exact-length cache
    for b in range(B):
        c1 = T.init_cache(cfg, 1, lens[b] + 4)
        lg, c1 = T.prefill(params, cfg, jnp.asarray(prompts[b])[None], c1)
        t1 = jnp.argmax(lg, -1)
        ref = [int(t1[0])]
        for s in range(3):
            lg, c1 = T.decode_step(params, cfg, t1, c1, lens[b] + s)
            t1 = jnp.argmax(lg, -1)
            ref.append(int(t1[0]))
        assert list(got[b]) == ref, (arch, b, list(got[b]), ref)


def test_scatter_cache_roundtrip():
    """`scatter_cache` writes a k-request cache into the right slots of the
    pool and leaves other slots untouched."""
    cfg = _reduced("zamba2-1.2b")
    pool = T.init_cache(cfg, 3, 8)
    pool = jax.tree.map(lambda l: jnp.ones_like(l), pool)
    sub = T.init_cache(cfg, 2, 8)
    sub = jax.tree.map(lambda l: jnp.full_like(l, 2), sub)
    out = T.scatter_cache(pool, sub, jnp.asarray([2, 0]))
    axes = T.cache_batch_axes(pool)

    def check(leaf, ax):
        leaf = np.asarray(leaf, np.float32)
        idx = [slice(None)] * leaf.ndim
        for slot, val in ((0, 2.0), (1, 1.0), (2, 2.0)):
            idx[ax] = slot
            assert (leaf[tuple(idx)] == val).all()
    jax.tree.map(check, out, axes)
