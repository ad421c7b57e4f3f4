"""Mapping-execution runtime tests (`repro.runtime`): artifact -> plan ->
artifact round trips, per-layer planned execution parity against the fp
reference (interpret mode), jit parity of the name-keyed backend, scan-
stacked binding/execution, conv im2col lowering, per-domain quant scales,
lowering validation, kernel capability selection, the serve fallback vote,
pipeline stage checkpointing, and the 3-domain gap9_like platform."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (MappingArtifact, Platform, SearchConfig,
                       SearchPipeline, lower, mlp_handle)
from repro.core import baselines as BL
from repro.data.pipeline import ImageTaskConfig, image_batch
from repro.models import _backend
from repro.runtime import (ExecutionError, ExecutionPlan, KERNEL_FP,
                           KERNEL_QUANT, KERNEL_SPLIT, KERNEL_SPLIT_TERNARY,
                           KERNEL_TERNARY, LayerPlan, LoweringError,
                           PlannedBackend, execute_conv_layer, execute_layer,
                           prepare_layer, reference_layer)
from repro.runtime.lower import select_kernel

TINY = SearchConfig(lam=1e-6, objective="latency", pretrain_steps=3,
                    search_steps=5, finetune_steps=2, batch=8, eval_batches=2)


def _data_fn(n_classes=10, img_hw=(4, 4)):
    task = ImageTaskConfig(n_classes=n_classes, img_hw=img_hw)
    return lambda step, batch: image_batch(task, step, batch)


def _toy_artifact(rng=None):
    """2-layer TPU-domain artifact + matching concrete params."""
    rng = rng or np.random.default_rng(0)
    spec = Platform.get("tpu_v5e").spec()
    a0 = np.array(([0] * 3 + [1]) * 16)            # 64 cols, mixed
    a1 = np.zeros(48, dtype=np.int64)              # all int8
    assigns = [a0, a1]
    counts = BL.counts_from_assignments(assigns, 2)
    plan_list = [("l0", None, True), ("l1", None, False)]
    art = MappingArtifact.from_search("toy", spec, plan_list, assigns,
                                      counts, platform="tpu_v5e")
    params = {
        "l0": {"w": jnp.asarray(rng.normal(size=(32, 64)) * 0.3, jnp.float32),
               "b": jnp.asarray(rng.normal(size=(64,)) * 0.1, jnp.float32)},
        "l1": {"w": jnp.asarray(rng.normal(size=(64, 48)) * 0.2,
                                jnp.float32)},
    }
    return art, params


# --------------------------------------------------------------------------
# (a) artifact -> plan -> artifact round trip
# --------------------------------------------------------------------------

def test_artifact_plan_artifact_roundtrip(tmp_path):
    art, params = _toy_artifact()
    plan = lower(art, params=params)
    assert [lp.name for lp in plan.layers] == ["l0", "l1"]
    for lp, a in zip(plan.layers, art.assignments()):
        # the permutation groups channels by domain, stably
        sorted_assign = a[lp.perm]
        assert (np.diff(sorted_assign) >= 0).all()
        np.testing.assert_array_equal(lp.perm, np.argsort(a, kind="stable"))
        # boundaries are the counts' cumulative sums; aligned ones are
        # block-multiples covering them
        np.testing.assert_array_equal(lp.boundaries, np.cumsum(lp.counts))
        for raw, al in zip(lp.boundaries, lp.aligned_boundaries):
            assert al % plan.block_n == 0 or al >= lp.c_out
            assert al >= min(raw, al)
        # plan -> artifact: counts and assignment are recoverable
        assert lp.counts == [int((a == i).sum()) for i in range(2)]
        rebuilt = np.empty_like(a)
        rebuilt[lp.perm] = sorted_assign
        np.testing.assert_array_equal(rebuilt, a)
    # searchability survives lowering
    assert plan["l0"].searchable and not plan["l1"].searchable
    # JSON round trip, to disk and back
    p = plan.save(tmp_path / "plan.json")
    loaded = ExecutionPlan.load(p)
    assert loaded.to_dict() == plan.to_dict()
    assert loaded.summary() == plan.summary()
    # future plan schemas are rejected, not misread
    doc = json.loads(p.read_text())
    doc["schema_version"] = 99
    with pytest.raises(ValueError, match="newer"):
        ExecutionPlan.from_dict(doc)


def test_v1_artifact_lowers_without_scales():
    """Migration: v1 documents (no scales) load and lower; executors fall
    back to max-abs scales of the bound weights."""
    art, params = _toy_artifact()
    doc = art.to_dict()
    doc["schema_version"] = 1
    for l in doc["layers"]:
        l.pop("scales", None)
    v1 = MappingArtifact.from_dict(doc)
    assert v1.schema_version == 1
    plan = lower(v1, params=params)
    lp = plan["l0"]
    w = params["l0"]["w"]
    assert lp.act_log_scale is None
    assert lp.w_log_scales == pytest.approx(
        [float(np.log(np.max(np.abs(np.asarray(w)))))] * 2)
    backend = PlannedBackend(plan, params)
    x = jnp.ones((4, 32), jnp.float32)
    assert backend("l0", params["l0"], x).shape == (4, 64)


# --------------------------------------------------------------------------
# kernel capability selection
# --------------------------------------------------------------------------

def test_select_kernel_capability_matrix():
    bits2 = [8, 16]
    assert select_kernel([10, 0], bits2) == (KERNEL_QUANT, "")
    assert select_kernel([0, 10], bits2) == (KERNEL_FP, "")
    assert select_kernel([5, 5], bits2) == (KERNEL_SPLIT, "")
    assert select_kernel([4, 0], [2, 16]) == (KERNEL_TERNARY, "")
    # ternary + int8 (DIANA mixed layer): the fused split_ternary kernel
    assert select_kernel([5, 5], [8, 2]) == (KERNEL_SPLIT_TERNARY, "")
    # quant domain ordered after the identity domain: split layout impossible
    k, note = select_kernel([5, 5], [16, 8])
    assert k == KERNEL_FP and "ordered before" in note
    # same for the ternary pairing: the int8 domain owns the low columns
    k, note = select_kernel([5, 5], [2, 8])
    assert k == KERNEL_FP and "ordered before" in note
    # ternary + identity has no fused kernel registered
    k, note = select_kernel([5, 5], [2, 16])
    assert k == KERNEL_FP and "no fused kernel" in note
    # three active domains exceed the fused kernels
    k, note = select_kernel([3, 3, 3], [8, 2, 16])
    assert k == KERNEL_FP and "3 active domains" in note


def test_kernel_registry_round_trip():
    """New pairings are ONE registration; bad registrations are rejected."""
    from repro.runtime import registry
    assert registry.kernel_for([8, 2]) == (KERNEL_SPLIT_TERNARY, "")
    with pytest.raises(ValueError, match="already registered"):
        registry.register_kernel(("q", "t"), KERNEL_SPLIT)
    with pytest.raises(ValueError, match="unknown kernel"):
        registry.register_kernel(("t", "f"), "nope")
    with pytest.raises(ValueError, match="unknown bit class"):
        registry.register_kernel(("x",), KERNEL_FP)
    try:  # a fresh pairing routes immediately, without touching lower.py
        registry.register_kernel(("t", "f"), KERNEL_SPLIT, "test-only")
        assert registry.kernel_for([2, 16]) == (KERNEL_SPLIT, "")
    finally:
        registry.unregister_kernel(("t", "f"))
    k, note = registry.kernel_for([2, 16])
    assert k == KERNEL_FP and "no fused kernel" in note


def test_platform_kernel_capabilities_introspection():
    caps = Platform.get("diana").kernel_capabilities()
    assert caps[("digital", "aimc")] == (KERNEL_SPLIT_TERNARY, "")
    assert caps[("digital",)] == (KERNEL_QUANT, "")
    assert caps[("aimc",)] == (KERNEL_TERNARY, "")
    g9 = Platform.get("gap9_like").kernel_capabilities()
    assert g9[("ne16", "analog")] == (KERNEL_SPLIT_TERNARY, "")
    assert g9[("ne16", "cluster_fp16")] == (KERNEL_SPLIT, "")
    k, note = g9[("analog", "cluster_fp16")]
    assert k == KERNEL_FP and note


def test_strict_lowering_rejects_capability_fallbacks():
    # ternary + identity has no fused kernel -> fp fallback, note carries
    # the layer name and the bits pair
    doc = {
        "schema_version": 2, "model": "mixed",
        "domains": [{"name": "aimc", "weight_bits": 2, "act_bits": 7},
                    {"name": "fp16", "weight_bits": 16, "act_bits": 16}],
        "layers": [{"name": "l", "searchable": True,
                    "assignment": [0, 1] * 8, "counts": [8, 8]}],
    }
    plan = lower(doc)                     # non-strict: fp fallback + note
    assert plan["l"].kernel == KERNEL_FP
    assert "l: " in plan["l"].note and "2-bit + 16-bit" in plan["l"].note
    assert plan.fallback_reasons() == {
        "no fused kernel for 2-bit + 16-bit domains": ["l"]}
    assert any("fallback x1" in line for line in plan.histogram_lines())
    with pytest.raises(LoweringError, match="no fused kernel"):
        lower(doc, strict=True)


def test_diana_mixed_layer_lowers_to_split_ternary():
    """The paper's headline platform: a digital+AIMC mixed layer lowers to
    the fused split_ternary kernel — no fp fallback, strict mode passes."""
    spec = Platform.get("diana").spec()   # digital int8 + ternary AIMC
    a = np.array([0, 1] * 8)
    art = MappingArtifact.from_search(
        "mixed", spec, [("l", None, True)], [a],
        BL.counts_from_assignments([a], 2))
    plan = lower(art, strict=True)        # strict: would raise on fallback
    assert plan["l"].kernel == KERNEL_SPLIT_TERNARY and not plan["l"].note


# --------------------------------------------------------------------------
# (b) planned execution parity (interpret mode)
# --------------------------------------------------------------------------

def _split_prepared(rng, m=16, k=64, n=256, boundary=128):
    assign = np.array([0] * boundary + [1] * (n - boundary))
    lp = LayerPlan(
        name="l", kernel=KERNEL_SPLIT, c_in=k, c_out=n,
        perm=np.arange(n), counts=[boundary, n - boundary],
        boundaries=[boundary, n], aligned_boundaries=[128, 256],
        w_log_scales=None, act_log_scale=None)
    w = jnp.asarray(rng.normal(size=(k, n)) * 0.25, jnp.float32)
    b = jnp.asarray(rng.normal(size=(n,)) * 0.1, jnp.float32)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    return prepare_layer(lp, w, b, domain_bits=[8, 16]), x


def test_planned_execution_matches_quantized_reference():
    """Pallas (interpret) vs the pure-jnp oracle: bit-tolerance parity on a
    layer wide enough that BOTH split domains execute."""
    prep, x = _split_prepared(np.random.default_rng(1))
    y_kernel = execute_layer(prep, x, interpret=True)
    y_oracle = execute_layer(prep, x, reference=True)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_oracle),
                               rtol=1e-4, atol=1e-4)


def test_block_n_agrees_between_plan_and_execution():
    """Plans lowered with a non-default block_n align boundaries with the
    SAME effective N-block the ops execute with (the ops clamp bn to
    min(bn, max(128, n)))."""
    art, params = _toy_artifact()
    for bn, expect_eff in ((256, 128), (128, 128)):   # c_out = 64 -> eff 128
        plan = lower(art, params=params, block_n=bn)
        lp = plan["l0"]
        assert lp.aligned_boundaries == [128, 128]
        backend = PlannedBackend(plan, params)
        prep = backend._by_name["l0"]
        assert prep.block_n == bn
        x = jnp.asarray(np.random.default_rng(3).normal(size=(4, 32)),
                        jnp.float32)
        y_kernel = execute_layer(prep, x, interpret=True)
        y_oracle = execute_layer(prep, x, reference=True)
        np.testing.assert_allclose(np.asarray(y_kernel),
                                   np.asarray(y_oracle),
                                   rtol=1e-4, atol=1e-4)


def test_planned_execution_vs_fp_reference_within_quant_tolerance():
    prep, x = _split_prepared(np.random.default_rng(2))
    y = np.asarray(execute_layer(prep, x, interpret=True), np.float64)
    y_fp = np.asarray(reference_layer(prep, x), np.float64)
    rel = np.linalg.norm(y - y_fp) / np.linalg.norm(y_fp)
    assert rel < 0.05, rel
    # the bf16 (identity) half must be much tighter than int8 quant error
    rel_hi = (np.linalg.norm(y[:, 128:] - y_fp[:, 128:])
              / np.linalg.norm(y_fp[:, 128:]))
    assert rel_hi < 0.01, rel_hi


def test_planned_model_execution_parity_mlp():
    """End-to-end deploy mode: a fixed-mapping search artifact lowered and
    executed through the façade's pluggable backend stays within quant
    tolerance of the fp forward pass."""
    handle = mlp_handle(in_dim=48, widths=(160, 144), n_classes=10)
    data_fn = _data_fn()
    assigns = [np.array([0] * 96 + [1] * 64),
               np.array([0] * 80 + [1] * 64),
               np.zeros(10, np.int64)]
    res = SearchPipeline.fixed_mapping(handle, assigns, "tpu_v5e",
                                       train_steps=2, config=TINY,
                                       data_fn=data_fn).run()
    art = res.artifact
    assert art.schema_version == 2
    assert art.layers[0]["scales"]["w_log_scales"] is not None
    plan = lower(art, params=res.params, handle=handle)
    assert plan.kernel_histogram() == {KERNEL_SPLIT: 2, KERNEL_QUANT: 1}
    backend = PlannedBackend(plan, res.params, handle=handle)
    assert backend.bound == [lp.name for lp in plan.layers]

    from repro.models import facades
    spec = Platform.get("tpu_v5e").spec()
    x, _ = data_fn(0, 8)
    y_dep = facades.mlp_apply(res.params, x, handle.config, spec,
                              mode="deploy", backend=backend)
    y_fp = facades.mlp_apply(res.params, x, handle.config, spec, mode="fp")
    rel = float(jnp.linalg.norm(y_dep - y_fp) / jnp.linalg.norm(y_fp))
    assert rel < 0.15, rel
    # without a backend the deploy mode still runs (discretized fallback)
    y_fb = facades.mlp_apply(res.params, x, handle.config, spec,
                             mode="deploy")
    assert np.isfinite(np.asarray(y_fb)).all()


def test_backend_declines_uncovered_layers():
    art, params = _toy_artifact()
    plan = lower(art, params=params)
    backend = PlannedBackend(plan, params)
    other = {"w": jnp.ones((32, 64), jnp.float32)}
    # unknown and unnamed layers decline; covered names execute
    assert backend("nope", other, jnp.ones((2, 32))) is None
    assert backend(None, other, jnp.ones((2, 32))) is None
    from repro.models import layers as L
    from repro.models.managed import matmul_backend
    with matmul_backend(backend):
        y = L.dense(other, jnp.ones((2, 32), jnp.float32))  # default path
        y2 = L.dense(other, jnp.ones((2, 32), jnp.float32), name="nope")
    np.testing.assert_allclose(np.asarray(y), 32.0)
    np.testing.assert_allclose(np.asarray(y2), 32.0)


def test_handle_plan_count_mismatch_is_execution_error():
    """Binding-phase failures are ExecutionErrors, not LoweringErrors."""
    art, params = _toy_artifact()
    plan = lower(art, params=params)

    class TwoLayerHandle:
        def layers(self, p):
            return [p["l0"]]  # one node for a two-layer plan

    with pytest.raises(ExecutionError, match="resolves 1 managed layers"):
        PlannedBackend(plan, params, handle=TwoLayerHandle())


# --------------------------------------------------------------------------
# jit parity: the name-keyed backend executes planned kernels INSIDE a trace
# --------------------------------------------------------------------------

def _single_layer_backend(kernel, rng, k=32, n=64):
    """(backend, params, name) with one layer lowered to ``kernel``."""
    domains = {
        KERNEL_QUANT: ([0] * n, [8, 16]),
        KERNEL_TERNARY: ([0] * n, [2, 16]),
        KERNEL_SPLIT: ([0] * (n // 2) + [1] * (n // 2), [8, 16]),
        KERNEL_FP: ([1] * n, [8, 16]),
    }
    assign, bits = domains[kernel]
    doc = {
        "schema_version": 2, "model": "jitparity",
        "domains": [{"name": f"d{i}", "weight_bits": b, "act_bits": 8}
                    for i, b in enumerate(bits)],
        "layers": [{"name": "l", "searchable": True,
                    "assignment": assign,
                    "counts": [assign.count(0), assign.count(1)]}],
    }
    params = {"l": {"w": jnp.asarray(rng.normal(size=(k, n)) * 0.3,
                                     jnp.float32),
                    "b": jnp.asarray(rng.normal(size=(n,)) * 0.1,
                                     jnp.float32)}}
    plan = lower(doc, params=params)
    assert plan["l"].kernel == kernel
    return PlannedBackend(plan, params, interpret=True), params, "l"


@pytest.mark.parametrize("kernel", [KERNEL_QUANT, KERNEL_TERNARY,
                                    KERNEL_SPLIT, KERNEL_FP])
def test_backend_jit_parity_per_kernel(kernel):
    """The planned output under jax.jit equals the eager planned output —
    the backend resolves by static name, so nothing falls back to the
    default path inside the trace."""
    rng = np.random.default_rng(7)
    backend, params, name = _single_layer_backend(kernel, rng)
    x = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
    y_eager = backend(name, params[name], x)
    y_jit = jax.jit(lambda p, xx: backend(name, p, xx))(params[name], x)
    np.testing.assert_allclose(np.asarray(y_jit), np.asarray(y_eager),
                               rtol=1e-5, atol=1e-5)
    # and the jitted output is genuinely the PLANNED one, not the fp path
    if kernel != KERNEL_FP:
        y_fp = x @ params[name]["w"] + params[name]["b"]
        assert not np.allclose(np.asarray(y_jit), np.asarray(y_fp),
                               rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------------------
# scan-stacked plans: base@r names bind and execute inside the layer scan
# --------------------------------------------------------------------------

def _stacked_artifact(rng, assigns_per_repeat, scales=True):
    """R-repeat stacked dense artifact + params {"units": ({"proj": ...},)}."""
    R = len(assigns_per_repeat)
    K = 16
    spec = Platform.get("tpu_v5e").spec()
    counts = BL.counts_from_assignments(assigns_per_repeat, 2)
    plan_list = [(f"units/0/proj@{r}", None, True) for r in range(R)]
    sc = None
    if scales:
        sc = [{"w_log_scales": [float(np.log(0.4 + 0.2 * r))] * 2,
               "act_log_scale": None} for r in range(R)]
    art = MappingArtifact.from_search("stacked", spec, plan_list,
                                      assigns_per_repeat, counts,
                                      platform="tpu_v5e", scales=sc)
    N = len(assigns_per_repeat[0])
    params = {"units": ({"proj": {
        "w": jnp.asarray(rng.normal(size=(R, K, N)) * 0.25, jnp.float32),
        "b": jnp.asarray(rng.normal(size=(R, N)) * 0.1, jnp.float32)}},)}
    return art, params, R, K


def _scan_planned(backend, x, R):
    """Execute ``units/0/proj`` for every repeat inside a jitted lax.scan
    (the transformer.backbone pattern: scan_slot publishes the index)."""
    def body(carry, ridx):
        with _backend.scan_slot(ridx):
            y = backend("units/0/proj", None, x)
        return carry, y

    @jax.jit
    def run():
        _, ys = jax.lax.scan(body, 0, jnp.arange(R))
        return ys
    return run()


def test_scan_stacked_plans_bind_and_execute_homogeneous():
    """All repeats bind (none silently fp) and the stacked execution inside
    a jitted scan matches per-repeat eager execution."""
    rng = np.random.default_rng(11)
    a = np.array(([0] * 3 + [1]) * 16)           # same split every repeat
    art, params, R, K = _stacked_artifact(rng, [a] * 3)
    plan = lower(art, params=params)
    backend = PlannedBackend(plan, params, interpret=True)
    assert backend.unbound == []
    assert backend.bound == [f"units/0/proj@{r}" for r in range(R)]
    from repro.runtime.execute import _StackedPrepared
    assert isinstance(backend._by_name["units/0/proj"], _StackedPrepared)

    x = jnp.asarray(rng.normal(size=(2, K)), jnp.float32)
    ys = _scan_planned(backend, x, R)
    for r in range(R):
        with _backend.scan_slot(r):
            y_eager = backend("units/0/proj", None, x)
        np.testing.assert_allclose(np.asarray(ys[r]), np.asarray(y_eager),
                                   rtol=1e-5, atol=1e-5)


def test_scan_stacked_heterogeneous_kernels_grouped():
    """Repeats with different kernels (split / quant / fp) still all bind;
    a traced scan index dispatches through lax.switch over the GROUPS."""
    rng = np.random.default_rng(12)
    N = 64
    assigns = [np.array([0] * 32 + [1] * 32),    # split_precision
               np.zeros(N, np.int64),            # quant_matmul
               np.ones(N, np.int64)]             # fp
    art, params, R, K = _stacked_artifact(rng, assigns)
    plan = lower(art, params=params)
    assert [lp.kernel for lp in plan.layers] == \
        [KERNEL_SPLIT, KERNEL_QUANT, KERNEL_FP]
    backend = PlannedBackend(plan, params, interpret=True)
    assert backend.unbound == []
    from repro.runtime.execute import _GroupedPrepared
    entry = backend._by_name["units/0/proj"]
    assert isinstance(entry, _GroupedPrepared) and entry.n_groups == 3

    x = jnp.asarray(rng.normal(size=(2, K)), jnp.float32)
    ys = _scan_planned(backend, x, R)
    for r in range(R):
        with _backend.scan_slot(r):
            y_eager = backend("units/0/proj", None, x)
        np.testing.assert_allclose(np.asarray(ys[r]), np.asarray(y_eager),
                                   rtol=1e-5, atol=1e-5)
    # outside any scan_slot the stacked plan fails LOUDLY, never silently fp
    with pytest.raises(ExecutionError, match="outside a scan_slot"):
        backend("units/0/proj", None, x)


def test_scan_stacked_repeating_pattern_groups_not_switches():
    """The common heterogeneous case — a few distinct mappings tiled across
    the depth — groups into G stacked gathers (G=2 here for R=6), and the
    grouped execution matches both eager per-repeat execution and the
    one-branch-per-repeat ``stack_mode="switch"`` baseline."""
    rng = np.random.default_rng(15)
    N = 64
    a_split = np.array([0] * 32 + [1] * 32)
    a_quant = np.zeros(N, np.int64)
    assigns = [a_split, a_quant] * 3                  # R=6, 2 distinct keys
    art, params, R, K = _stacked_artifact(rng, assigns)
    plan = lower(art, params=params)
    grouped = PlannedBackend(plan, params, interpret=True)
    switch = PlannedBackend(plan, params, interpret=True,
                            stack_mode="switch")
    from repro.runtime.execute import _GroupedPrepared, _SwitchPrepared
    g_entry = grouped._by_name["units/0/proj"]
    assert isinstance(g_entry, _GroupedPrepared) and g_entry.n_groups == 2
    assert isinstance(switch._by_name["units/0/proj"], _SwitchPrepared)

    x = jnp.asarray(rng.normal(size=(2, K)), jnp.float32)
    ys_grouped = _scan_planned(grouped, x, R)
    ys_switch = _scan_planned(switch, x, R)
    np.testing.assert_allclose(np.asarray(ys_grouped),
                               np.asarray(ys_switch), rtol=1e-5, atol=1e-5)
    for r in range(R):
        with _backend.scan_slot(r):
            y_eager = grouped("units/0/proj", None, x)
        np.testing.assert_allclose(np.asarray(ys_grouped[r]),
                                   np.asarray(y_eager),
                                   rtol=1e-5, atol=1e-5)


def test_scan_stacked_quant_stack_skips_fp_weights():
    """Homogeneous quant stacks don't hold R full-precision weight copies
    (the quant kernel only reads the int8 codes) and still execute within
    quant tolerance."""
    rng = np.random.default_rng(14)
    a = np.zeros(64, np.int64)                    # all int8 -> quant_matmul
    art, params, R, K = _stacked_artifact(rng, [a] * 3, scales=False)
    backend = PlannedBackend(lower(art, params=params), params,
                             interpret=True)
    entry = backend._by_name["units/0/proj"]
    assert entry._w_perm is None
    x = jnp.asarray(rng.normal(size=(2, K)), jnp.float32)
    for r in range(R):
        with _backend.scan_slot(r):
            y = backend("units/0/proj", None, x)
        ref = x @ params["units"][0]["proj"]["w"][r] + \
            params["units"][0]["proj"]["b"][r]
        rel = float(jnp.linalg.norm(y - ref) / jnp.linalg.norm(ref))
        assert rel < 0.05, rel


def test_scan_stacked_repeat_count_mismatch_rejected():
    """A plan covering fewer repeats than the model's stack must not bind:
    out-of-range jnp.take inside the scan would produce NaN silently."""
    rng = np.random.default_rng(13)
    a = np.array(([0] * 3 + [1]) * 16)
    art, params, R, K = _stacked_artifact(rng, [a] * 2)   # plan: 2 repeats
    # model: 3 repeats
    params = {"units": ({"proj": {
        "w": jnp.asarray(rng.normal(size=(3, K, len(a))) * 0.25, jnp.float32),
        "b": jnp.asarray(rng.normal(size=(3, len(a))) * 0.1, jnp.float32)}},)}
    plan = lower(art, params=params)
    with pytest.raises(ExecutionError, match="2 repeats.*carries 3"):
        PlannedBackend(plan, params)


# --------------------------------------------------------------------------
# conv lowering: im2col onto the planned dense kernels
# --------------------------------------------------------------------------

def _conv_prep(rng, kh, kw, ci, co, kernel=KERNEL_FP, bits=(8, 16)):
    assign = {KERNEL_FP: [1] * co, KERNEL_SPLIT:
              [0] * (co // 2) + [1] * (co - co // 2)}[kernel]
    counts = [assign.count(0), assign.count(1)]
    lp = LayerPlan(name="c", kernel=kernel, c_in=kh * kw * ci, c_out=co,
                   perm=np.argsort(np.asarray(assign), kind="stable"),
                   counts=counts, boundaries=list(np.cumsum(counts)),
                   aligned_boundaries=[128, 128], w_log_scales=None,
                   act_log_scale=None)
    w = jnp.asarray(rng.normal(size=(kh, kw, ci, co)) * 0.2, jnp.float32)
    b = jnp.asarray(rng.normal(size=(co,)) * 0.1, jnp.float32)
    return prepare_layer(lp, w, b, domain_bits=list(bits)), w, b


@pytest.mark.parametrize("stride,padding", [(1, "SAME"), (2, "SAME"),
                                            (1, "VALID")])
def test_conv_im2col_matches_lax_conv(stride, padding):
    """fp-kernel conv execution through im2col == lax.conv_general_dilated
    (same SAME/VALID semantics, bias applied)."""
    rng = np.random.default_rng(21)
    prep, w, b = _conv_prep(rng, 3, 3, 5, 8)
    x = jnp.asarray(rng.normal(size=(2, 12, 12, 5)), jnp.float32)
    y = execute_conv_layer(prep, x, stride=stride, padding=padding)
    ref_y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + b
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref_y),
                               rtol=2e-5, atol=2e-5)


def test_planned_conv_through_managed_backend_jit():
    """A CNN-style artifact binds conv weights and `managed.conv2d` executes
    them through the planned split kernel under jax.jit, within quant
    tolerance of the fp conv."""
    from repro.models import managed as mg
    rng = np.random.default_rng(22)
    ci, co = 4, 16
    assign = [0] * 11 + [1] * 5
    doc = {
        "schema_version": 2, "model": "convtest",
        "domains": [{"name": "int8", "weight_bits": 8, "act_bits": 8},
                    {"name": "bf16", "weight_bits": 16, "act_bits": 16}],
        "layers": [{"name": "c", "searchable": True, "assignment": assign,
                    "counts": [11, 5]}],
    }
    params = {"c": {"w": jnp.asarray(rng.normal(size=(3, 3, ci, co)) * 0.3,
                                     jnp.float32),
                    "b": jnp.zeros((co,), jnp.float32)}}
    plan = lower(doc, params=params)
    assert plan["c"].kernel == KERNEL_SPLIT
    backend = PlannedBackend(plan, params, interpret=True)
    assert backend.bound == ["c"]
    x = jnp.asarray(rng.normal(size=(2, 8, 8, ci)), jnp.float32)
    fwd = jax.jit(lambda p, xx: mg.conv2d(p["c"], xx, name="c"))
    with mg.matmul_backend(backend):
        y_planned = fwd(params, x)
    y_fp = mg.conv2d(params["c"], x)
    rel = float(jnp.linalg.norm(y_planned - y_fp) /
                jnp.maximum(jnp.linalg.norm(y_fp), 1e-9))
    assert rel < 0.1, rel
    # dense-style call on a conv-bound name is a loud mismatch
    with pytest.raises(ExecutionError, match="conv weight"):
        backend("c", params["c"], x.reshape(2, -1))


def test_grouped_conv_declines_with_reason():
    """Depthwise/grouped convs have no im2col lowering: the backend declines
    at trace time and records why (surfaced by serve's coverage check)."""
    from repro.models import managed as mg
    rng = np.random.default_rng(23)
    c = 8
    doc = {
        "schema_version": 2, "model": "dw",
        "domains": [{"name": "int8", "weight_bits": 8, "act_bits": 8}],
        "layers": [{"name": "dw", "searchable": False,
                    "assignment": [0] * c, "counts": [c]}],
    }
    params = {"dw": {"w": jnp.asarray(rng.normal(size=(3, 3, 1, c)),
                                      jnp.float32),
                     "b": jnp.zeros((c,), jnp.float32)}}
    backend = PlannedBackend(lower(doc, params=params), params,
                             interpret=True)
    x = jnp.asarray(rng.normal(size=(1, 6, 6, c)), jnp.float32)
    with mg.matmul_backend(backend):
        y = mg.conv2d(params["dw"], x, groups=c, name="dw")  # default path
    assert np.isfinite(np.asarray(y)).all()
    assert "dw" in backend.runtime_declines
    assert "grouped conv" in backend.runtime_declines["dw"]


# --------------------------------------------------------------------------
# per-domain per-column quant scales (multi-quantized-domain plans)
# --------------------------------------------------------------------------

def test_prepare_layer_per_domain_column_steps():
    """Each active quantized domain's columns carry THAT domain's dequant
    step — not a uniform step from quantized[0] (wrong for plans with
    several quantized domains, e.g. 3-domain gap9_like)."""
    from repro.core import quant
    rng = np.random.default_rng(31)
    n0, n1, n2 = 10, 6, 4            # int8 | ternary | fp16 (gap9-like)
    N = n0 + n1 + n2
    ls = [0.3, -0.9, 0.0]
    lp = LayerPlan(name="g", kernel=KERNEL_QUANT, c_in=8, c_out=N,
                   perm=np.arange(N), counts=[n0, n1, n2],
                   boundaries=[n0, n0 + n1, N],
                   aligned_boundaries=[128, 128, 128],
                   w_log_scales=ls, act_log_scale=None)
    w = jnp.asarray(rng.normal(size=(8, N)) * 0.5, jnp.float32)
    prep = prepare_layer(lp, w, domain_bits=[8, 2, 16])
    sw = np.asarray(prep.sw)
    step0 = np.exp(ls[0]) / quant.qlevels(8)
    step1 = np.exp(ls[1]) / quant.qlevels(2)
    np.testing.assert_allclose(sw[:n0], step0, rtol=1e-6)
    np.testing.assert_allclose(sw[n0:n0 + n1], step1, rtol=1e-6)
    # identity-domain columns inherit the DRIVING quantized domain's step
    # (they execute in int8 only as conservative block padding)
    np.testing.assert_allclose(sw[n0 + n1:], step0, rtol=1e-6)
    # codes * step reconstruct each domain's columns with ITS scale
    deq = np.asarray(prep.w_q, np.float32)[:, :N] * sw[None, :]
    wf = np.asarray(w)
    for cols, bits, s in [(slice(0, n0), 8, ls[0]),
                          (slice(n0, n0 + n1), 2, ls[1])]:
        expect = np.asarray(quant.fake_quant(jnp.asarray(wf[:, cols]),
                                             jnp.asarray(s), bits))
        np.testing.assert_allclose(deq[:, cols], expect, atol=1e-6)


# --------------------------------------------------------------------------
# serve coverage gate
# --------------------------------------------------------------------------

def test_serve_require_full_coverage_exits_nonzero():
    from repro.launch import serve

    class FakeBackend:
        unbound = ["l1"]
        runtime_declines = {}
    with pytest.raises(SystemExit) as ei:
        serve.check_coverage("serve", FakeBackend(), True)
    assert ei.value.code == 2

    class Declined:
        unbound = []
        runtime_declines = {"dw": "grouped conv"}
    with pytest.raises(SystemExit):
        serve.check_coverage("serve", Declined(), True)

    class Full:
        unbound = []
        runtime_declines = {}
    serve.check_coverage("serve", Full(), True)   # no exit


# --------------------------------------------------------------------------
# (c) lowering validation
# --------------------------------------------------------------------------

def test_lowering_rejects_shape_mismatched_artifact():
    art, params = _toy_artifact()
    bad = {"l0": {"w": jnp.zeros((32, 60), jnp.float32)},
           "l1": params["l1"]}
    with pytest.raises(LoweringError,
                       match="assigns 64 output channels.*60 channels"):
        lower(art, params=bad)
    # inconsistent stored counts are rejected too
    doc = art.to_dict()
    doc["layers"][0]["counts"] = [1, 63]
    with pytest.raises(LoweringError, match="disagree"):
        lower(doc, params=params)
    # out-of-range domain indices are rejected
    doc = art.to_dict()
    doc["layers"][0]["assignment"][0] = 7
    with pytest.raises(LoweringError, match="references domain"):
        lower(doc, params=params)
    # a layer name that resolves nowhere means the wrong model was given
    with pytest.raises(LoweringError, match="no param node"):
        lower(art, params={"l1": params["l1"]})


# --------------------------------------------------------------------------
# serve fallback: searchable-only majority vote
# --------------------------------------------------------------------------

def test_apply_mapping_artifact_counts_searchable_votes_only():
    from repro.configs import base as cfgbase
    from repro.launch import serve
    cfgbase.load_all()
    cfg = cfgbase.reduce_for_smoke(cfgbase.get("yi-9b"))
    spec = Platform.get("tpu_v5e").spec()
    # a wide PINNED layer on int8 (domain 0) vs a small searchable layer
    # whose channels chose bf16: only the searchable layer may vote
    a_pinned = np.zeros(512, np.int64)
    a_search = np.ones(32, np.int64)
    art = MappingArtifact.from_search(
        "vote", spec, [("pinned", None, False), ("chosen", None, True)],
        [a_pinned, a_search],
        BL.counts_from_assignments([a_pinned, a_search], 2))
    new_cfg, dom = serve.apply_mapping_artifact(cfg, art)
    assert dom["name"] == "bf16"
    assert new_cfg.serve_weight_dtype == cfg.serve_weight_dtype  # unchanged
    # with no searchable layers at all, every layer votes (fallback)
    art_all_pinned = MappingArtifact.from_search(
        "vote2", spec, [("pinned", None, False)], [a_pinned],
        BL.counts_from_assignments([a_pinned], 2))
    _, dom = serve.apply_mapping_artifact(cfg, art_all_pinned)
    assert dom["name"] == "int8"


# --------------------------------------------------------------------------
# pipeline stage checkpointing
# --------------------------------------------------------------------------

def test_pipeline_checkpoint_resume_restarts_at_search(tmp_path):
    handle = mlp_handle(in_dim=48, widths=(24,), n_classes=10)
    data_fn = _data_fn()
    full = SearchPipeline(handle, "tpu_v5e", config=TINY, data_fn=data_fn,
                          checkpoint_dir=str(tmp_path / "ck")).run()
    resumed = SearchPipeline(handle, "tpu_v5e", config=TINY, data_fn=data_fn,
                             resume_from=str(tmp_path / "ck")).run()
    # the resumed run skipped Pretrain...
    assert "pretrain" not in resumed.history and "pretrain" in full.history
    # ...and is bit-identical from DNASSearch onward
    assert resumed.accuracy == full.accuracy
    assert resumed.latency == full.latency
    for a, b in zip(resumed.assignments, full.assignments):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(
        np.asarray(resumed.history["search"]),
        np.asarray(full.history["search"]))
    with pytest.raises(FileNotFoundError, match="no committed"):
        SearchPipeline(handle, "tpu_v5e", config=TINY, data_fn=data_fn,
                       resume_from=str(tmp_path / "nope")).run()


# --------------------------------------------------------------------------
# gap9_like: third registered platform, 3 domains
# --------------------------------------------------------------------------

def test_gap9_platform_three_domains():
    plat = Platform.get("gap9_like")
    assert [d.name for d in plat.domains] == ["ne16", "analog",
                                              "cluster_fp16"]
    assert [d.weight_bits for d in plat.domains] == [8, 2, 16]
    spec = plat.spec()
    assert spec.n_domains == 3 and spec.act_bits == 7
    cm = plat.cost_model()
    from repro.core.cost_models import LayerGeometry
    lat = cm.latency(LayerGeometry(c_in=16, c_out=30),
                     jnp.asarray([10.0, 10.0, 10.0]))
    assert lat.shape == (3,)
    assert float(lat[1]) < float(lat[0]) < float(lat[2])  # analog fastest


def test_gap9_search_and_lowering():
    handle = mlp_handle(in_dim=48, widths=(24,), n_classes=10)
    res = SearchPipeline(handle, "gap9_like", config=TINY,
                         data_fn=_data_fn()).run()
    assert all(len(c) == 3 for c in res.counts)
    assert len(res.artifact.domains) == 3
    # lowering handles 3-domain layers: single-domain ones get their kernel,
    # >2-active ones record the fp fallback reason
    plan = lower(res.artifact, params=res.params, handle=handle)
    for lp in plan.layers:
        assert lp.kernel in (KERNEL_QUANT, KERNEL_TERNARY, KERNEL_SPLIT,
                             KERNEL_SPLIT_TERNARY, KERNEL_FP)
        if len(lp.active_domains()) > 2:
            assert lp.kernel == KERNEL_FP and lp.note


# --------------------------------------------------------------------------
# grouped/depthwise conv im2col lowering (block-diagonal zero-embedding)
# --------------------------------------------------------------------------

def test_grouped_conv_planned_matches_lax_conv():
    """A plan with ``groups`` executes a depthwise conv through the im2col
    kernels via block-diagonal weight expansion — close to the exact
    lax grouped conv (quantization tolerance), jit included."""
    from repro.models import managed as mg
    rng = np.random.default_rng(29)
    c, g = 12, 4                      # 4 groups x 3 in-ch x 3 out-ch
    doc = {
        "schema_version": 2, "model": "gc",
        "domains": [{"name": "int8", "weight_bits": 8, "act_bits": 8}],
        "layers": [{"name": "gc", "searchable": False, "groups": g,
                    "assignment": [0] * c, "counts": [c]}],
    }
    params = {"gc": {"w": jnp.asarray(rng.normal(size=(3, 3, c // g, c)) * 0.4,
                                      jnp.float32),
                     "b": jnp.asarray(rng.normal(size=(c,)) * 0.1,
                                      jnp.float32)}}
    plan = lower(doc, params=params)
    assert plan["gc"].groups == g
    backend = PlannedBackend(plan, params, interpret=True)
    assert backend.fully_covered
    x = jnp.asarray(rng.normal(size=(2, 6, 6, c)), jnp.float32)
    fwd = jax.jit(lambda p, xx: mg.conv2d_linear(p["gc"], xx, groups=g,
                                                 name="gc"))
    with mg.matmul_backend(backend):
        y = fwd(params, x)
    assert not backend.runtime_declines
    y_ref = mg.conv2d_linear(params["gc"], x, groups=g)
    rel = float(jnp.linalg.norm(y - y_ref) /
                jnp.maximum(jnp.linalg.norm(y_ref), 1e-9))
    assert rel < 0.1, rel
    # group-count mismatch at the call site is a loud error, not silent fp
    with pytest.raises(ExecutionError, match="groups"):
        backend("gc", params["gc"], x,
                conv={"stride": 1, "padding": "SAME", "groups": 2})


def test_grouped_conv_expansion_is_block_diagonal():
    """`_expand_grouped`: input-channel block i only feeds output block i;
    off-diagonal entries are exactly zero (they quantize to code 0)."""
    from repro.runtime.execute import _expand_grouped
    rng = np.random.default_rng(31)
    w = jnp.asarray(rng.normal(size=(1, 1, 2, 6)), jnp.float32)   # g=3
    full = np.asarray(_expand_grouped(w, 3))[0, 0]                # (6, 6)
    for gi in range(3):
        blk = full[gi * 2:(gi + 1) * 2, gi * 2:(gi + 1) * 2]
        np.testing.assert_array_equal(blk, np.asarray(w)[0, 0][:,
                                      gi * 2:(gi + 1) * 2])
        off = np.delete(full[gi * 2:(gi + 1) * 2], np.s_[gi * 2:(gi + 1) * 2],
                        axis=1)
        assert (off == 0).all()


@pytest.mark.slow
def test_mbv1_artifact_full_coverage():
    """ROADMAP open item: mbv1's own emitted artifact (depthwise convs
    included) lowers and binds with FULL coverage — no trace-time declines,
    no unbound layers."""
    from repro.launch.train import emit_static_mapping
    from repro.models import cnn as C
    from repro.models import managed as mg
    cfg = C.get_config("mobilenetv1_tiny")
    init_fn, apply_fn, plan_fn = C.get_model(cfg)
    params = init_fn(jax.random.PRNGKey(0), cfg, None)
    hints = {n: (g, s) for (n, g, s) in plan_fn(cfg)}
    import tempfile
    from pathlib import Path
    with tempfile.TemporaryDirectory() as td:
        art = emit_static_mapping(params, cfg, "diana",
                                  Path(td) / "m.json", plan_hints=hints)
    dw_layers = [l for l in art.layers if l.get("groups", 1) > 1]
    assert len(dw_layers) == 13          # every depthwise block emitted
    assert all(not l["searchable"] for l in dw_layers)  # pinned (paper rule)
    plan = lower(art, params=params)
    backend = PlannedBackend(plan, params, interpret=True)
    assert backend.fully_covered, backend.unbound
    x = jax.random.normal(jax.random.PRNGKey(1), (2, *cfg.img_hw, cfg.in_ch),
                          jnp.float32)
    fwd = jax.jit(lambda p, xb: apply_fn(p, xb, cfg, None, "fp", 1.0))
    with mg.matmul_backend(backend):
        y = jax.block_until_ready(fwd(params, x))
    assert not backend.runtime_declines, backend.runtime_declines
    assert np.isfinite(np.asarray(y)).all()


def test_grouped_conv_plan_json_roundtrip():
    lp = LayerPlan(name="dw", kernel=KERNEL_QUANT, c_in=9 * 4, c_out=4,
                   perm=np.arange(4), counts=[4], boundaries=[4],
                   aligned_boundaries=[128], w_log_scales=[0.1],
                   act_log_scale=None, groups=4)
    plan = ExecutionPlan(model="m", domains=[{"name": "d", "weight_bits": 8,
                                              "act_bits": 8}], layers=[lp])
    back = ExecutionPlan.from_json(plan.to_json())
    assert back["dw"].groups == 4


# --------------------------------------------------------------------------
# gpu_tc_like: GPU tensor-core platform (int8 + fp16 pair)
# --------------------------------------------------------------------------

def test_gpu_tc_platform_registered_and_fuses_split_precision():
    plat = Platform.get("gpu_tc_like")
    assert [d.name for d in plat.domains] == ["tc_int8", "tc_fp16"]
    assert [d.weight_bits for d in plat.domains] == [8, 16]
    caps = plat.kernel_capabilities()
    # the mixed pairing fuses (int8 ordered first), no fallback note
    kernel, note = caps[("tc_int8", "tc_fp16")]
    assert kernel == KERNEL_SPLIT and not note
    from repro.core.cost_models import LayerGeometry
    lat = plat.cost_model().latency(LayerGeometry(c_in=16, c_out=32),
                                    jnp.asarray([8.0, 8.0]))
    assert lat.shape == (2,)
    assert float(lat[0]) < float(lat[1])     # int8 MMA @2x throughput


def test_gpu_tc_search_lowers_executably():
    handle = mlp_handle(in_dim=48, widths=(24,), n_classes=10)
    res = SearchPipeline(handle, "gpu_tc_like", config=TINY,
                         data_fn=_data_fn()).run()
    plan = lower(res.artifact, params=res.params, handle=handle)
    for lp in plan.layers:
        assert lp.kernel in (KERNEL_QUANT, KERNEL_SPLIT, KERNEL_FP)
        assert not lp.note                   # every pairing has a kernel
