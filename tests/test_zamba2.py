"""Zamba2-7B's hybrid layer and grouped Mamba2 against the plain float32
reference (`bench/reference/zamba2.py`) at a small size on the CPU: paged
cache, chunked prefill and then decoding through it, with the default
(fp) path and with the planned int8 path's jnp oracle.  Each planted fault
(the shared block's output added to the residual stream, the blocks
swapped, the Mamba2 groups collapsed to one, the softmax scaled by
head_dim ** -0.5) must fail the fp comparison.  Also: the options the
program refuses, and that the engine's steps take the planned weights as
arguments, so no executable embeds one."""
import dataclasses
import importlib.util
import inspect
import sys
import types
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.configs import zamba2_7b
from repro.models import ssm as S
from repro.models import transformer as T
from repro.models.managed import matmul_backend

ROOT = Path(__file__).resolve().parents[1]
PATTERN = ("mamba", "hybrid", "hybrid", "mamba", "hybrid", "hybrid")
# two groups of two Mamba2 heads, two shared blocks used twice each
SIZES = {"n_layers": 6, "d_model": 128, "vocab": 96, "pattern": list(PATTERN),
         "n_heads": 4, "n_kv_heads": 4, "head_dim": 64, "d_ff": 96,
         "rope_theta": 10000.0, "ssm_state": 16, "mamba_expand": 2,
         "mamba_headdim": 64, "mamba_d_conv": 4, "mamba_ngroups": 2,
         "mem_blocks": 2, "adapter_rank": 8, "tie_embeddings": True}
PROMPTS = (13, 6)          # prompt lengths of the two slots
LENGTH = 19                # tokens per sequence: prompt, then decoded
CHUNK, PAGE = 8, 4
# Tolerances, as shares of the largest logit.  Without a cache the fp
# path is float32 throughout, as the reference is: 3e-6 measured.  Served
# through the cache, its keys, values and attention probabilities are
# bfloat16: 2.0% measured.  A planted fault moved the logits by 62-129%.
# The planned path rounds every projection's weights and inputs to int8 at
# one static scale, exp(3.0); it is judged as the benchmark judges a cell,
# by how far below the reference's best logit its first-ranked token lies:
# 0.42-0.52 on three seeds, where the reference in int4 reads 1.01-1.21
# (largest logits 0.85-0.95).
EXACT_TOL, FP_TOL, FAULT_MIN, PLANNED_GAP = 1e-4, 0.04, 0.3, 0.75


def reference():
    path = ROOT / "bench" / "reference" / "zamba2.py"
    spec = importlib.util.spec_from_file_location("zamba2_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def small_cfg(**kw):
    base.load_all()
    return dataclasses.replace(
        base.get("zamba2-7b"), n_layers=SIZES["n_layers"], pattern=PATTERN,
        d_model=128, n_heads=4, n_kv_heads=4, head_dim=64, d_ff=96,
        vocab=96, ssm_state=16, adapter_rank=8,
        attn_scale=(64 / 2) ** -0.5, param_dtype="float32", **kw)


def small_params(cfg, seed=0):
    """`init_lm`'s weights, with every norm gain drawn away from one."""
    params = T.init_lm(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)

    def gain(path, a):
        if getattr(path[-1], "key", None) == "scale":
            return a * jnp.asarray(1 + 0.2 * rng.standard_normal(a.shape),
                                   a.dtype)
        return a
    return jax.tree_util.tree_map_with_path(gain, params)


def sequences(seed=0):
    rng = np.random.default_rng(seed + 100)
    return rng.integers(0, SIZES["vocab"], (len(PROMPTS), LENGTH))


def program_logits(model, cfg, params, seqs, backend=None):
    """Logits at every position of each slot's sequence, served as the
    engine serves it: a paged cache, the prompts prefilled in chunks of
    CHUNK (the slots at their own positions), then one decode step per
    further token, fed the sequence's own next token."""
    B, L = seqs.shape
    W = -(-L // PAGE)
    caches = model.init_paged_cache(cfg, B, 1 + B * W, PAGE)
    pages = (1 + np.arange(B * W, dtype=np.int32)).reshape(B, W)
    chunk = jax.jit(partial(model.prefill_chunk, cfg=cfg, full_logits=True))
    decode = jax.jit(partial(model.decode_step, cfg=cfg))
    out = np.zeros((B, L, cfg.vocab), np.float32)
    fill = np.zeros(B, np.int32)
    with matmul_backend(backend):
        while (fill < PROMPTS).any():
            valid = np.minimum(CHUNK, np.asarray(PROMPTS) - fill)
            tokens = np.zeros((B, CHUNK), np.int32)
            for b in range(B):
                tokens[b, :valid[b]] = seqs[b, fill[b]:fill[b] + valid[b]]
            lg, caches = chunk(params, tokens=tokens, caches=caches,
                               index=fill, valid=valid, pages=pages)
            for b in range(B):
                out[b, fill[b]:fill[b] + valid[b]] = np.asarray(
                    lg[b, :valid[b]])
            fill = fill + valid
        while (fill < L).any():
            active = fill < L
            tok = seqs[np.arange(B), np.minimum(fill, L - 1)]
            lg, caches = decode(params, token=tok, caches=caches,
                                index=fill, active=active, pages=pages)
            for b in np.nonzero(active)[0]:
                out[b, fill[b]] = np.asarray(lg[b])
            fill = fill + active
    return out


def reference_logits(params, seqs):
    ref = reference()
    with jax.default_matmul_precision("highest"):
        return np.stack([np.asarray(ref.logits(params, SIZES, s,
                                                np.arange(len(s))))
                         for s in seqs])


def rel_error(a, b):
    return float(np.abs(a - b).max() / np.abs(b).max())


def widest_gap(logits, want):
    """How far below ``want``'s best logit the token ``logits`` ranks
    first lies, at the worst position."""
    pick = np.take_along_axis(want, logits.argmax(-1)[..., None], -1)
    return float((want.max(-1) - pick[..., 0]).max())


@pytest.fixture(scope="module")
def case():
    cfg = small_cfg()
    params = small_params(cfg)
    seqs = sequences()
    return cfg, params, seqs, reference_logits(params, seqs)


def test_uncached_forward_is_the_reference(case):
    cfg, params, seqs, want = case
    x = params["emb"][seqs]
    h, _, _ = T.backbone(params, cfg, x, jnp.arange(seqs.shape[1])[None])
    assert rel_error(np.asarray(h @ params["emb"].T), want) < EXACT_TOL


def test_fp_path_matches_the_reference(case):
    cfg, params, seqs, want = case
    got = program_logits(T, cfg, params, seqs)
    assert rel_error(got, want) < FP_TOL


def test_planned_path_matches_the_reference(case, tmp_path):
    from repro.launch.train import emit_static_mapping
    from repro.runtime import PlannedBackend, lower
    cfg, params, seqs, want = case
    art = emit_static_mapping(params, cfg, "tpu_v5e",
                              tmp_path / "mapping.json", act_log_scale=3.0)
    plan = lower(art, params=params)
    backend = PlannedBackend(plan, params, reference=True)
    assert not backend.unbound
    names = {lp.name.partition("@")[0] for lp in plan.layers}
    assert {"shared/0/attn/wq", "shared/1/ffn/gate_up", "units/1/lora_a",
            "units/1/lora_b", "units/1/linear",
            "units/1/mamba/in_proj"} <= names
    got = program_logits(T, cfg, params, seqs, backend)
    assert widest_gap(got, want) < PLANNED_GAP
    with jax.default_matmul_precision("highest"):
        control = np.stack([np.asarray(reference().logits(
            params, SIZES, s, np.arange(len(s)), bits=4, act_log_scale=3.0))
            for s in seqs])
    assert widest_gap(control, want) > PLANNED_GAP


def _mutant(module, old=None, new=None, **overrides):
    """A copy of ``module`` with its source text ``old`` replaced by
    ``new`` (the planted fault), and its names in ``overrides`` rebound."""
    src = inspect.getsource(module)
    if old is not None:
        assert src.count(old) == 1, old
        src = src.replace(old, new)
    mod = types.ModuleType(f"{module.__name__}_mutant")
    mod.__dict__.update(__name__=mod.__name__, __file__=module.__file__)
    sys.modules[mod.__name__] = mod
    try:
        exec(compile(src, module.__file__, "exec"), mod.__dict__)
    finally:
        del sys.modules[mod.__name__]
    mod.__dict__.update(overrides)
    return mod


FAULTS = {
    "y_added_to_the_residual": (
        T, "        return x + mo, nc, aux\n",
        "        return x + y + mo, nc, aux\n"),
    "blocks_swapped": (
        T, "b = u % cfg.mem_blocks", "b = (u + 1) % cfg.mem_blocks"),
    "groups_collapsed_to_one": (
        S, "Bt.reshape(B, S, G, N), Ct.reshape(B, S, G, N)",
        "jnp.repeat(Bt[:, :, None, :N], G, 2), "
        "jnp.repeat(Ct[:, :, None, :N], G, 2)"),
    "scale_of_head_dim": (
        T, "rotary=not cross, scale=cfg.attn_scale)",
        "rotary=not cross, scale=None)"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_fails_the_comparison(case, fault):
    cfg, params, seqs, want = case
    module, old, new = FAULTS[fault]
    model = (_mutant(T, S=_mutant(S, old, new)) if module is S
             else _mutant(T, old, new))
    got = program_logits(model, cfg, params, seqs)
    assert rel_error(got, want) > FAULT_MIN


@pytest.mark.parametrize("key,value", [
    ("use_shared_attention_adapter", True), ("use_long_context", True),
    ("add_bias_linear", True), ("use_mem_rope", False),
    ("time_step_limit", [0.0, 0.1]), ("mamba_headdim", 128)])
def test_unsupported_zamba2_options_are_refused(key, value):
    with pytest.raises(ValueError, match=key):
        zamba2_7b.from_published({**zamba2_7b.PUBLISHED, key: value})


def test_registered_config_is_the_published_one():
    base.load_all()
    cfg = base.get("zamba2-7b")
    hybrid = [i for i, k in enumerate(cfg.pattern) if k == "hybrid"]
    assert hybrid == [6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77]
    assert (cfg.n_layers, cfg.d_model, cfg.hd, cfg.d_ff) == \
        (81, 3584, 224, 14336)
    assert T._mamba_cfg(cfg).conv_dim == 7168 + 2 * 2 * 64
    assert cfg.attn_scale == pytest.approx(112 ** -0.5)
    # the first stage: every shared block used once
    stage = dataclasses.replace(cfg, n_layers=12, pattern=cfg.pattern[:12])
    assert T.hybrid_blocks(stage) == {("units", 6): 0, ("units", 11): 1}


def test_a_block_that_changes_between_repeats_is_refused():
    cfg = dataclasses.replace(small_cfg(), pattern=("mamba", "hybrid"),
                              n_layers=4)
    with pytest.raises(ValueError, match="change from repeat to repeat"):
        T.hybrid_blocks(cfg)
