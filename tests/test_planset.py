"""Multi-plan precision bank tests (`repro.runtime.PlanSet` + the serving
features it powers).

Covers: prepared-buffer dedup accounting (identical variants cost one bind;
divergent variants share only coinciding layers; a two-variant bank stays
strictly below two independent binds when any layer coincides), variant
selection parity against a single-plan bind, per-variant coverage diffs by
layer NAME, self-speculative decoding token identity vs target-only greedy
serving on mixed-length traces (attention-only yi-9b AND hybrid zamba2,
whose recurrent state exercises the replay path), SLO-routed serving parity
+ per-class metrics, jit-safe non-greedy sampling (off by default,
seed-deterministic), and the engine's multi-plan validation errors.
"""
import jax
import numpy as np
import pytest

from repro.api import MappingArtifact
from repro.configs import base as cfgbase
from repro.models import transformer as T
from repro.models._backend import plan_variant
from repro.runtime import PlannedBackend, PlanSet, lower
from repro.serving import Engine, SamplingParams, synthetic_trace

jnp = jax.numpy


@pytest.fixture(scope="module", autouse=True)
def _load():
    cfgbase.load_all()


def _reduced(arch):
    return cfgbase.reduce_for_smoke(cfgbase.get(arch))


def _artifact(cfg, params, tmp_path, bias=None, name="m.json"):
    """Static diana artifact (static act scales — the engine's per-request
    reproducibility precondition) with an optional precision-bank bias."""
    from repro.launch.train import emit_static_mapping
    return emit_static_mapping(params, cfg, "diana", tmp_path / name,
                               act_log_scale=2.0, bias=bias)


def _flip_layer(art, layer_name, domain=1):
    """A copy of ``art`` with one layer's channels forced to ``domain`` —
    the minimal divergent variant (every other layer coincides)."""
    doc = art.to_dict()
    hit = False
    for layer in doc["layers"]:
        if layer["name"] == layer_name:
            n = len(layer["assignment"])
            layer["assignment"] = [domain] * n
            counts = [0] * len(doc["domains"])
            counts[domain] = n
            layer["counts"] = counts
            hit = True
    assert hit, f"no layer named {layer_name!r}"
    return MappingArtifact.from_dict(doc)


@pytest.fixture(scope="module")
def yi(tmp_path_factory):
    """Reduced yi-9b + params + a fully-digital target artifact."""
    tmp = tmp_path_factory.mktemp("planset_yi")
    cfg = _reduced("yi-9b")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    target = _artifact(cfg, params, tmp, bias=("digital", 1.0))
    return cfg, params, target, tmp


def _tokens(results):
    return {r.rid: list(r.tokens) for r in results}


# --------------------------------------------------------------------------
# dedup accounting
# --------------------------------------------------------------------------

def test_identical_variants_cost_one_bind(yi):
    cfg, params, target, _ = yi
    plan = lower(target, params=params)
    single = PlannedBackend(plan, params)
    bank = PlanSet({"a": lower(target, params=params),
                    "b": lower(target, params=params)}, params, default="a")
    rep = bank.memory_report()
    # two identical variants hold ONE set of prepared buffers
    assert rep["prepared_bytes"] == single.prepared_bytes()
    assert rep["sum_variant_bytes"] == 2 * rep["prepared_bytes"]
    assert rep["dedup_saved_bytes"] == rep["prepared_bytes"]
    # every prepared entry (plain layer or whole scan stack) is shared
    n_entries = len(bank.variant("a").by_name)
    assert n_entries > 0 and len(rep["shared_layers"]) == n_entries
    assert all(set(vs) == {"a", "b"}
               for vs in rep["shared_layers"].values())


def test_divergent_variants_share_only_coinciding_layers(yi):
    cfg, params, target, _ = yi
    draft_art = _flip_layer(target, "head", domain=1)
    bank = PlanSet({"target": lower(target, params=params),
                    "draft": lower(draft_art, params=params)},
                   params, default="target")
    assert bank.fully_covered
    rep = bank.memory_report()
    shared = rep["shared_layers"]
    # everything except the flipped head coincides and is shared once
    assert "head" not in shared
    n_entries = len(bank.variant("target").by_name)
    assert len(shared) == n_entries - 1
    # the bank is STRICTLY below two independent binds (ISSUE criterion)
    two_binds = (PlannedBackend(lower(target, params=params),
                                params).prepared_bytes() +
                 PlannedBackend(lower(draft_art, params=params),
                                params).prepared_bytes())
    assert 0 < rep["prepared_bytes"] < two_binds
    assert rep["dedup_saved_bytes"] == two_binds - rep["prepared_bytes"]


def test_fully_divergent_variants_share_nothing(yi):
    cfg, params, target, tmp = yi
    draft_art = _artifact(cfg, params, tmp, bias=("aimc", 1.0),
                          name="allaimc.json")
    bank = PlanSet({"target": lower(target, params=params),
                    "draft": lower(draft_art, params=params)},
                   params, default="target")
    rep = bank.memory_report()
    assert rep["shared_layers"] == {}
    assert rep["dedup_saved_bytes"] == 0


# --------------------------------------------------------------------------
# variant selection + coverage diff
# --------------------------------------------------------------------------

def test_variant_selection_matches_single_plan_bind(yi):
    cfg, params, target, tmp = yi
    draft_art = _artifact(cfg, params, tmp, bias=("aimc", 1.0),
                          name="sel.json")
    bank = PlanSet({"target": lower(target, params=params),
                    "draft": lower(draft_art, params=params)},
                   params, default="target")
    draft_only = PlannedBackend(lower(draft_art, params=params), params)
    tokens = jnp.arange(12, dtype=jnp.int32).reshape(1, 12) % cfg.vocab
    caches = T.init_cache(cfg, 1, 16)
    from repro.models.managed import matmul_backend

    def prefill_logits(backend, variant):
        with matmul_backend(backend):
            logits, _ = T.prefill(params, cfg, tokens, caches,
                                  variant=variant)
        return np.asarray(logits)

    # default variant == the target plan; the draft variant under the bank
    # is bit-identical to binding the draft plan alone
    np.testing.assert_array_equal(prefill_logits(bank, "draft"),
                                  prefill_logits(draft_only, None))
    assert not np.array_equal(prefill_logits(bank, None),
                              prefill_logits(bank, "draft"))
    # the context-manager route publishes the same trace-static key
    with matmul_backend(bank), plan_variant("draft"):
        logits, _ = T.prefill(params, cfg, tokens, caches)
    np.testing.assert_array_equal(np.asarray(logits),
                                  prefill_logits(bank, "draft"))


def test_coverage_diff_names_layers_per_variant(yi):
    cfg, params, target, _ = yi
    doc = target.to_dict()
    kept = doc["layers"][0]["name"]
    doc["layers"] = [l for l in doc["layers"] if l["name"] == kept]
    partial = MappingArtifact.from_dict(doc)
    bank = PlanSet({"full": lower(target, params=params),
                    "partial": lower(partial, params=params)},
                   params, default="full")
    assert bank.coverage_diff() == {}          # nothing unbound anywhere
    assert bank.fully_covered

    # an artifact naming a layer the params don't have leaves it UNBOUND
    # on that variant only, and the diff reports the NAME, not a count
    # (lowered without params — WITH params the name mismatch is already a
    # LoweringError; bind-time resolution is what coverage_diff audits)
    doc = target.to_dict()
    doc["layers"][0] = dict(doc["layers"][0], name="units/9/no_such")
    ghost = MappingArtifact.from_dict(doc)
    bank = PlanSet({"full": lower(target, params=params),
                    "ghost": lower(ghost)},
                   params, default="full")
    diff = bank.coverage_diff()
    assert list(diff) == ["ghost"]
    assert diff["ghost"] == ["units/9/no_such"]
    assert not bank.fully_covered


def test_unknown_variant_fails_loud(yi):
    cfg, params, target, _ = yi
    from repro.runtime import ExecutionError
    bank = PlanSet({"only": lower(target, params=params)}, params)
    with plan_variant("nope"), pytest.raises(ExecutionError,
                                             match="unknown plan variant"):
        bank("head", None, None)      # resolution fails before execution


# --------------------------------------------------------------------------
# self-speculative decoding
# --------------------------------------------------------------------------

def _spec_bank(cfg, params, tmp, draft_bias):
    target = _artifact(cfg, params, tmp, bias=("digital", 1.0),
                       name="spec_t.json")
    draft = _artifact(cfg, params, tmp, bias=draft_bias, name="spec_d.json")
    return PlanSet({"target": lower(target, params=params),
                    "draft": lower(draft, params=params)},
                   params, default="target")


def _run_spec_vs_target(cfg, params, bank, *, draft_k=4):
    trace = synthetic_trace(4, vocab=cfg.vocab, seed=3, min_prompt=4,
                            max_prompt=10, min_new=4, max_new=10)
    spec = Engine(cfg, params, max_batch=2, max_len=64, backend=bank,
                  kv_layout="paged", speculate=("draft", "target"),
                  draft_k=draft_k)
    ref = Engine(cfg, params, max_batch=2, max_len=64, backend=bank,
                 kv_layout="paged")
    return spec, _tokens(spec.run(trace)), _tokens(ref.run(trace))


def test_speculative_token_identity_attention_only(yi, tmp_path):
    """yi-9b (attention-only, replay-free): a genuinely divergent ternary-
    tinted draft must still yield TOKEN-IDENTICAL output — acceptance only
    controls speed."""
    cfg, params, _, _ = yi
    bank = _spec_bank(cfg, params, tmp_path, ("aimc", 0.05))
    spec, got, want = _run_spec_vs_target(cfg, params, bank)
    assert got == want
    st = spec.stats
    assert st["spec_rounds"] > 0
    assert 0 <= st["spec_acceptance"] <= 1.0
    assert st["spec_committed"] == sum(len(t) - 1 for t in got.values())


def test_speculative_token_identity_hybrid_replay(tmp_path):
    """zamba2 (hybrid SSM+attention): partial accepts must REPLAY the
    committed tokens over the snapshot recurrent state — token identity
    here pins the rollback machinery."""
    cfg = _reduced("zamba2-1.2b")
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    bank = _spec_bank(cfg, params, tmp_path, ("aimc", 0.05))
    spec, got, want = _run_spec_vs_target(cfg, params, bank)
    assert got == want
    assert spec._has_recurrent            # the replay path is in play
    assert spec.stats["spec_rounds"] > 0


def test_speculative_identical_draft_accepts_everything(yi, tmp_path):
    """draft == target: every commit-eligible draft token must be accepted
    (acceptance exactly 1.0) and rounds retire whole k+1 blocks."""
    cfg, params, target, _ = yi
    bank = PlanSet({"target": lower(target, params=params),
                    "draft": lower(target, params=params)},
                   params, default="target")
    spec, got, want = _run_spec_vs_target(cfg, params, bank)
    assert got == want
    assert spec.stats["spec_acceptance"] == 1.0
    assert spec.stats["spec_tokens_per_round"] > 1.0


# --------------------------------------------------------------------------
# SLO routing
# --------------------------------------------------------------------------

def test_slo_routing_parity_and_metrics(yi, tmp_path):
    """Routed requests get their class's variant with numerics identical
    to serving them ALONE under that variant, and `summarize` breaks out
    per-class tails."""
    cfg, params, target, _ = yi
    draft_art = _artifact(cfg, params, tmp_path, bias=("aimc", 1.0),
                          name="slo.json")
    bank = PlanSet({"default": lower(target, params=params),
                    "cheap": lower(draft_art, params=params)},
                   params, default="default")
    trace = synthetic_trace(4, vocab=cfg.vocab, seed=5, min_prompt=4,
                            max_prompt=8, min_new=3, max_new=6,
                            slo_classes=["batch", "interactive"])
    eng = Engine(cfg, params, max_batch=2, max_len=64, backend=bank,
                 kv_layout="paged",
                 slo_routes={"interactive": "cheap", "batch": "default"})
    got = eng.run(trace)
    tokens = _tokens(got)
    # oracle: each request served ALONE under its routed variant
    for req in trace:
        variant = {"interactive": "cheap", "batch": "default"}[req.slo]
        solo_bank = PlanSet(
            {"v": lower(draft_art if variant == "cheap" else target,
                        params=params)}, params)
        solo = Engine(cfg, params, max_batch=1, max_len=64,
                      backend=solo_bank, kv_layout="paged")
        want = _tokens(solo.run([req]))
        assert tokens[req.rid] == want[req.rid], req.rid
    from repro.serving import summarize
    summary = summarize(got, eng.stats["wall_s"])
    assert set(summary["by_slo"]) == {"batch", "interactive"}
    for cls in ("batch", "interactive"):
        assert summary["by_slo"][cls]["requests"] == 2


def test_slo_unrouted_class_fails_loud(yi):
    cfg, params, target, _ = yi
    bank = PlanSet({"default": lower(target, params=params)}, params)
    eng = Engine(cfg, params, max_batch=2, max_len=64, backend=bank,
                 kv_layout="paged", slo_routes={"gold": "default"})
    trace = synthetic_trace(2, vocab=cfg.vocab, slo_classes=["silver"])
    with pytest.raises(ValueError, match="no route"):
        eng.run(trace)


# --------------------------------------------------------------------------
# non-greedy sampling
# --------------------------------------------------------------------------

def test_sampling_params_validation():
    with pytest.raises(ValueError):
        SamplingParams(temperature=0.0)
    with pytest.raises(ValueError):
        SamplingParams(temperature=1.0, top_p=0.0)
    with pytest.raises(ValueError):
        SamplingParams(temperature=1.0, top_p=1.5)


def test_sampling_off_by_default_and_seed_deterministic(yi):
    """No `sampling` -> greedy (the historical engine output); with
    sampling, the SAME seed reproduces the run and a different seed
    diverges — per-slot PRNG state survives continuous batching."""
    cfg, params, _, _ = yi
    trace = synthetic_trace(3, vocab=cfg.vocab, seed=7, min_prompt=4,
                            max_prompt=8, min_new=4, max_new=6)

    def run(sampling):
        eng = Engine(cfg, params, max_batch=2, max_len=64,
                     kv_layout="paged", sampling=sampling)
        return _tokens(eng.run(trace))

    greedy = run(None)
    hot = SamplingParams(temperature=5.0, top_p=0.9, seed=11)
    a, b = run(hot), run(hot)
    assert a == b                                 # seed-deterministic
    assert run(SamplingParams(temperature=5.0, top_p=0.9, seed=12)) != a
    assert a != greedy                            # it actually samples


# --------------------------------------------------------------------------
# engine validation
# --------------------------------------------------------------------------

def test_engine_multiplan_validation_errors(yi):
    cfg, params, target, _ = yi
    bank = PlanSet({"target": lower(target, params=params),
                    "draft": lower(target, params=params)},
                   params, default="target")
    mk = lambda **kw: Engine(cfg, params, max_batch=2, max_len=64, **kw)
    with pytest.raises(ValueError, match="pair of variant names"):
        mk(backend=bank, speculate="draft")
    with pytest.raises(ValueError, match="requires kv_layout='paged'"):
        mk(backend=bank, kv_layout="dense",
           speculate=("draft", "target"))
    with pytest.raises(ValueError, match="greedy-only"):
        mk(backend=bank, kv_layout="paged", speculate=("draft", "target"),
           sampling=SamplingParams(temperature=1.0))
    with pytest.raises(ValueError, match="mutually exclusive"):
        mk(backend=bank, kv_layout="paged", speculate=("draft", "target"),
           slo_routes={"x": "draft"})
    with pytest.raises(ValueError, match="draft_k"):
        mk(backend=bank, kv_layout="paged", speculate=("draft", "target"),
           draft_k=0)
    with pytest.raises(ValueError, match="is not bound"):
        mk(backend=bank, kv_layout="paged", speculate=("tiny", "target"))
    with pytest.raises(ValueError, match="multi-variant PlanSet"):
        mk(backend=None, kv_layout="paged", speculate=("draft", "target"))
    with pytest.raises(ValueError, match="is not bound"):
        mk(backend=bank, kv_layout="paged", slo_routes={"gold": "nope"})
    with pytest.raises(ValueError, match="requires kv_layout='paged'"):
        mk(backend=bank, kv_layout="dense", slo_routes={"gold": "draft"})


# --------------------------------------------------------------------------
# prepared arrays as arguments of the engine's steps
# --------------------------------------------------------------------------

_DTYPE_BYTES = {"i1": 1, "i8": 1, "ui8": 1, "i16": 2, "bf16": 2, "f16": 2,
                "i32": 4, "ui32": 4, "f32": 4, "i64": 8, "f64": 8}


def _largest_constant(lowered) -> int:
    """Bytes of the largest constant in a lowered program's StableHLO."""
    import math
    import re
    largest = 0
    for line in lowered.as_text().splitlines():
        if "stablehlo.constant" not in line:
            continue
        m = re.findall(r"tensor<((?:\d+x)*)([a-z]+\d+)>", line)
        if m:
            dims, dt = m[-1]
            n = math.prod(int(d) for d in dims.split("x") if d)
            largest = max(largest, n * _DTYPE_BYTES[dt])
    return largest


@pytest.fixture(scope="module")
def wide(tmp_path_factory):
    """A planned hybrid model whose FFN gate/up weights are 2 MiB in int8,
    bound to an engine."""
    import dataclasses
    from repro.launch.train import emit_static_mapping
    tmp = tmp_path_factory.mktemp("planset_wide")
    cfg = dataclasses.replace(_reduced("zamba2-7b"), d_ff=16384)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    art = emit_static_mapping(params, cfg, "tpu_v5e", tmp / "m.json",
                              act_log_scale=3.0)
    backend = PlannedBackend(lower(art, params=params), params)
    eng = Engine(cfg, params, backend=backend, max_batch=2, max_len=32,
                 page_size=8, prefill_chunk=8)
    return cfg, params, backend, eng


def _step_args(eng):
    B, W = eng.max_batch, eng.pages_per_slot
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    caches = T.paged_cache_specs(eng.cfg, B, eng.num_pages + 1,
                                 eng.page_size)
    keys = jax.ShapeDtypeStruct((B, 2), jnp.uint32)
    chunk = (eng._model, i32(B, eng.prefill_chunk), caches, i32(B), i32(B),
             i32(B, W), None, keys)
    decode = (eng._model, i32(B), caches, i32(B),
              jax.ShapeDtypeStruct((B,), jnp.bool_), i32(B, W), keys,
              jax.ShapeDtypeStruct((B,), jnp.float32))
    return chunk, decode


def test_engine_steps_embed_no_weight(wide):
    cfg, params, backend, eng = wide
    assert max(int(a.nbytes) for a in backend.weights()
               if a.dtype == jnp.int8) >= 2 << 20
    chunk, decode = _step_args(eng)
    with eng._ctx():
        lowered = [eng._chunk.lower(*chunk), eng._decode_paged.lower(*decode)]
        # the same decode step closing over the bound arrays embeds them:
        # the reading below sees a weight where one is baked in
        closed = jax.jit(lambda p, *a: T.decode_step(
            p, cfg, a[0], a[1], a[2], active=a[3], pages=a[4])).lower(
                params, *decode[1:6])
    assert all(_largest_constant(lw) < 1 << 20 for lw in lowered)
    assert _largest_constant(closed) >= 2 << 20


def test_weights_are_each_prepared_array_once(yi):
    cfg, params, target, _ = yi
    bank = PlanSet({"a": lower(target, params=params),
                    "b": lower(target, params=params)}, params)
    weights = bank.weights()
    assert len({id(a) for a in weights}) == len(weights)
    assert sum(int(a.nbytes) for a in weights) == bank.prepared_bytes()


def test_traced_reads_the_given_arrays_then_restores(yi):
    cfg, params, target, _ = yi
    backend = PlannedBackend(lower(target, params=params), params,
                             reference=True)
    x = jnp.ones((3, cfg.d_model), jnp.float32)
    name = next(n for n, e in backend._by_name.items()
                if not hasattr(e, "execute"))
    bound = backend(name, None, x)
    weights = backend.weights()
    with backend.traced(tuple(jnp.zeros_like(a) for a in weights)):
        assert float(jnp.abs(backend(name, None, x)).max()) == 0.0
    np.testing.assert_array_equal(backend(name, None, x), bound)
    assert all(a is b for a, b in zip(backend.weights(), weights))
    with pytest.raises(Exception, match="prepared arrays given"):
        with backend.traced(weights[:-1]):
            pass


@pytest.mark.parametrize("field", ["w_q", "sw"])
def test_an_array_outside_the_slots_is_refused(yi, monkeypatch, field):
    """A prepared array that `_entry_slots` does not list would be closed
    over, and embedded, by every step: the bank refuses to bind."""
    from repro.runtime import execute
    cfg, params, target, _ = yi
    monkeypatch.setattr(execute, "_PREP_ARRAY_FIELDS", tuple(
        f for f in execute._PREP_ARRAY_FIELDS if f != field))
    with pytest.raises(execute.ExecutionError, match="outside _entry_slots"):
        PlannedBackend(lower(target, params=params), params)
