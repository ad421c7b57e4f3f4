"""Config-driven LM assembly for all 10 assigned architectures.

Layers are organized as ``pattern_repeats`` repeats of the config's block
``pattern`` and executed with ``jax.lax.scan`` over the repeats (stacked
params, leading axis R) — this keeps HLO size and compile time bounded for
40-layer x 8k-wide archs (DESIGN.md §5).  zamba2's two remainder blocks run
unscanned; its shared transformer block's weights are closure-captured by the
scan body (shared across repeats), with per-use input norms stacked.  The
``hybrid`` kind (Zamba2-7B) holds its ``mem_blocks`` shared blocks under
``params["shared"]`` (a list), used in turn by the hybrid layers, and reads
the token embeddings (the backbone's input) beside the hidden state.

Public API (cfg: ArchConfig is static/hashable):
  init_lm(key, cfg)                          -> params
  lm_loss(params, cfg, batch)                -> scalar loss   (training)
  prefill(params, cfg, tokens, cache, ...)   -> (last_logits, cache)
  decode_step(params, cfg, token, cache, i)  -> (logits, cache)
  init_cache(cfg, B, S_max)                  -> cache pytree  (concrete)
  cache_specs(cfg, B, S_max)                 -> cache pytree  (ShapeDtypeStruct)
  scatter_cache(caches, sub, slots)          -> caches with sub written at slots

Continuous-batching serving (`repro.serving`) drives the same entry points
with per-slot state: ``prefill(..., lengths=)`` ragged-prefills right-padded
prompts, ``decode_step(..., index=(B,), active=)`` writes and masks the KV
cache at each slot's own length, and `scatter_cache` admits freshly
prefilled requests into freed slots of the cache pool.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import _backend
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S

_j = _backend.join


# --------------------------------------------------------------- kind specs

def _attn_cfg(cfg: ArchConfig, causal=True, cross=False) -> A.AttnConfig:
    return A.AttnConfig(
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.hd, rope_theta=cfg.rope_theta,
        sliding_window=None if cross else cfg.sliding_window,
        causal=causal and not cross, rotary=not cross, scale=cfg.attn_scale)


def _mla_cfg(cfg: ArchConfig) -> A.MLAConfig:
    m = cfg.mla
    return A.MLAConfig(d_model=cfg.d_model, n_heads=cfg.n_heads,
                       kv_lora_rank=m.kv_lora_rank, qk_nope_dim=m.qk_nope_dim,
                       qk_rope_dim=m.qk_rope_dim, v_head_dim=m.v_head_dim,
                       rope_theta=cfg.rope_theta)


def _mamba_cfg(cfg: ArchConfig) -> S.Mamba2Config:
    return S.Mamba2Config(d_model=cfg.d_model, d_state=cfg.ssm_state,
                          ngroups=cfg.mamba_ngroups)


def _xlstm_cfg(cfg: ArchConfig) -> S.XLSTMConfig:
    return S.XLSTMConfig(d_model=cfg.d_model, n_heads=cfg.n_heads)


def _dtype(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.param_dtype == "bfloat16" else jnp.float32


# --------------------------------------------------------------- block init

def init_block(key, cfg: ArchConfig, kind: str, layer_idx: int = 0):
    dt = _dtype(cfg)
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    p: dict = {"norm1": L.init_norm(d, dt, cfg.norm)}
    if kind == "attn":
        p["attn"] = A.init_attn(ks[0], _attn_cfg(cfg), dt)
        if cfg.moe is not None and layer_idx >= cfg.moe_first_dense:
            p["moe"] = M.init_moe(ks[1], d, cfg.moe, dt)
            if cfg.moe_dense_residual:
                p["ffn"] = L.init_ffn(ks[2], d, cfg.dense_ff, cfg.gated_ffn, dt)
                p["norm2"] = L.init_norm(d, dt, cfg.norm)
        elif cfg.moe is not None:  # first-dense MoE layer
            p["ffn"] = L.init_ffn(ks[2], d, cfg.dense_ff, cfg.gated_ffn, dt)
            p["norm2"] = L.init_norm(d, dt, cfg.norm)
        elif cfg.d_ff:
            p["ffn"] = L.init_ffn(ks[2], d, cfg.d_ff, cfg.gated_ffn, dt)
            if not cfg.parallel_block:
                p["norm2"] = L.init_norm(d, dt, cfg.norm)
    elif kind == "mla":
        p["attn"] = A.init_mla(ks[0], _mla_cfg(cfg), dt)
        if layer_idx < cfg.moe_first_dense:
            p["ffn"] = L.init_ffn(ks[2], d, cfg.dense_ff, cfg.gated_ffn, dt)
        else:
            p["moe"] = M.init_moe(ks[1], d, cfg.moe, dt)
        p["norm2"] = L.init_norm(d, dt, cfg.norm)
    elif kind == "cross":
        p["attn"] = A.init_attn(ks[0], _attn_cfg(cfg, cross=True), dt)
        p["gate"] = jnp.zeros((), dt)  # llama-vision gated cross-attn
        p["ffn"] = L.init_ffn(ks[2], d, cfg.d_ff, cfg.gated_ffn, dt)
        p["norm2"] = L.init_norm(d, dt, cfg.norm)
    elif kind == "dec":
        p["attn"] = A.init_attn(ks[0], _attn_cfg(cfg), dt)
        p["xattn"] = A.init_attn(ks[3], _attn_cfg(cfg, cross=True), dt)
        p["normx"] = L.init_norm(d, dt, cfg.norm)
        p["ffn"] = L.init_ffn(ks[2], d, cfg.d_ff, cfg.gated_ffn, dt)
        p["norm2"] = L.init_norm(d, dt, cfg.norm)
    elif kind == "mamba":
        p["mamba"] = S.init_mamba2(ks[0], _mamba_cfg(cfg), dt)
    elif kind == "mlstm":
        p["core"] = S.init_mlstm(ks[0], _xlstm_cfg(cfg), dt)
    elif kind == "slstm":
        p["core"] = S.init_slstm(ks[0], _xlstm_cfg(cfg), dt)
    elif kind == "shared_attn":
        # per-use input norm only; weights live in params["shared"]
        pass
    elif kind == "hybrid":
        # the layer's Mamba2 and its input norm, and the use's own weights:
        # the LoRA on the shared FFN's gate/up and the output projection
        p["mamba"] = S.init_mamba2(ks[0], _mamba_cfg(cfg), dt)
        p["lora_a"] = L.init_dense(ks[1], d, cfg.adapter_rank, dt)
        p["lora_b"] = L.init_dense(ks[2], cfg.adapter_rank, 2 * cfg.d_ff, dt)
        p["linear"] = L.init_dense(ks[3], d, d, dt)
    else:
        raise ValueError(kind)
    return p


def init_shared_block(key, cfg: ArchConfig):
    """zamba2 shared transformer block (one copy)."""
    dt = _dtype(cfg)
    ks = jax.random.split(key, 3)
    return {
        "attn": A.init_attn(ks[0], _attn_cfg(cfg), dt),
        "ffn": L.init_ffn(ks[1], cfg.d_model, cfg.d_ff, cfg.gated_ffn, dt),
        "norm2": L.init_norm(cfg.d_model, dt, cfg.norm),
    }


def init_hybrid_block(key, cfg: ArchConfig):
    """One of the ``hybrid`` kind's shared blocks: attention over the
    2*d_model-wide concat(x, embeddings) back to d_model, then a gated FFN
    whose gate and up projections are one (d_model, 2*d_ff) matrix."""
    dt = _dtype(cfg)
    ks = jax.random.split(key, 4)
    d, hd = cfg.d_model, cfg.hd
    attn = A.init_attn(ks[0], dataclasses.replace(_attn_cfg(cfg),
                                                  d_model=2 * d), dt)
    attn["wo"] = L.init_dense(ks[1], cfg.n_heads * hd, d, dt)
    return {
        "norm1": L.init_norm(2 * d, dt, cfg.norm),
        "attn": attn,
        "norm2": L.init_norm(d, dt, cfg.norm),
        "ffn": {"gate_up": L.init_dense(ks[2], d, 2 * cfg.d_ff, dt),
                "down": L.init_dense(ks[3], cfg.d_ff, d, dt)},
    }


def hybrid_blocks(cfg: ArchConfig) -> dict:
    """The shared block each hybrid layer uses, keyed ``("units", i)`` by
    pattern position and ``("rem", i)`` by remainder position: the u-th
    hybrid layer of the stack uses block u mod ``mem_blocks``.  A pattern
    whose repeats would use different blocks at one position is refused:
    the scan body must name its block statically."""
    period = len(cfg.pattern)
    reps = cfg.n_layers // period
    places = [("units", i) for _ in range(reps) for i in range(period)] + \
        [("rem", i) for i in range(cfg.n_layers - reps * period)]
    out: dict = {}
    u = 0
    for place in places:
        if cfg.pattern[place[1] % period] != "hybrid":
            continue
        b = u % cfg.mem_blocks
        if out.setdefault(place, b) != b:
            raise ValueError(
                f"{cfg.name}: pattern position {place[1]} uses shared blocks "
                f"that change from repeat to repeat ({cfg.mem_blocks} blocks)")
        u += 1
    return out


# --------------------------------------------------------------- block apply

def block_apply(p, x, kind, cfg: ArchConfig, positions, *, cache=None,
                cache_index=None, cross_kv=None, chunked=False, shared=None,
                name=None, length_mask=None, pages=None, emb=None,
                block=None):
    """One block. Returns (x, new_cache, aux_loss).

    ``name`` is the block's params-pytree path prefix (``"units/3"``,
    ``"rem/0"``, ``"first_dense"``); it is threaded into every projection's
    matmul-backend call so a name-keyed planned backend (see
    `repro.models._backend`) resolves the layer statically — including under
    `jax.jit` and inside the layer scan.  Shared-block weights always use the
    fixed ``"shared/..."`` names (one copy, many call sites).

    ``cache_index`` may be a scalar (whole batch at one position) or a
    ``(B,)`` array of per-slot cache positions, and ``length_mask`` (B, S)
    marks the valid tokens of a ragged batch — together these are the
    continuous-batching serving path: recurrent/MoE layers suppress masked
    tokens exactly, attention writes and masks the KV cache per slot.

    ``pages`` (B, W) switches the attention KV leaves to the paged layout
    (shared page pool + per-slot page table, `attention.paged_update` /
    `paged_gather`); per-slot state (recurrent, encoder memory) is O(1) per
    slot and stays slot-indexed either way.

    A ``hybrid`` block runs shared block ``block`` of ``shared`` over
    concat(x, ``emb``) and adds its output, through the use's ``linear``,
    to its Mamba2 layer's input: x + Mamba2(RMSNorm(x + y)).  Its cache
    holds the attention KV ("k", "v") and the Mamba2 state ("ssm",
    "conv")."""
    aux = 0.0
    if kind in ("attn", "mla"):
        h = L.norm(p["norm1"], x, cfg.norm)
        if kind == "attn":
            ao, nc = A.gqa(p["attn"], h, positions, _attn_cfg(cfg),
                           cache=cache, cache_index=cache_index,
                           chunked=chunked, pages=pages,
                           write_mask=length_mask, name=_j(name, "attn"))
        else:
            ao, nc = A.mla(p["attn"], h, positions, _mla_cfg(cfg),
                           cache=cache, cache_index=cache_index,
                           chunked=chunked, pages=pages,
                           write_mask=length_mask, name=_j(name, "attn"))
        if cfg.parallel_block and "ffn" in p:
            x = x + ao + L.ffn(p["ffn"], h, cfg.act, _j(name, "ffn"))
        else:
            x = x + ao
            if "moe" in p:
                h2 = L.norm(p.get("norm2", p["norm1"]), x, cfg.norm)
                mo, ml = M.moe_ffn(p["moe"], h2, cfg.moe, name=_j(name, "moe"),
                                   length_mask=length_mask)
                if "ffn" in p:  # arctic dense residual in parallel with MoE
                    mo = mo + L.ffn(p["ffn"], h2, cfg.act, _j(name, "ffn"))
                x = x + mo
                aux = aux + ml["load_balance"]
            elif "ffn" in p:
                x = x + L.ffn(p["ffn"], L.norm(p["norm2"], x, cfg.norm),
                              cfg.act, _j(name, "ffn"))
        return x, nc, aux
    if kind == "cross":
        h = L.norm(p["norm1"], x, cfg.norm)
        new_cache = None
        if cache is not None and cross_kv is not None:      # prefill: store
            new_cache = {"ck": cross_kv[0].astype(jnp.bfloat16),
                         "cv": cross_kv[1].astype(jnp.bfloat16)}
        elif cache is not None:                              # decode: reuse
            cross_kv = (cache["ck"], cache["cv"])
            new_cache = cache
        ao, _ = A.gqa(p["attn"], h, positions, _attn_cfg(cfg, cross=True),
                      kv_override=cross_kv, name=_j(name, "attn"))
        x = x + jnp.tanh(p["gate"]) * ao
        x = x + L.ffn(p["ffn"], L.norm(p["norm2"], x, cfg.norm), cfg.act,
                      _j(name, "ffn"))
        return x, new_cache, aux
    if kind == "dec":
        h = L.norm(p["norm1"], x, cfg.norm)
        self_cache = None
        if cache is not None:
            self_cache = {"k": cache["k"], "v": cache["v"]}
        ao, nc = A.gqa(p["attn"], h, positions, _attn_cfg(cfg),
                       cache=self_cache, cache_index=cache_index,
                       chunked=chunked, pages=pages, write_mask=length_mask,
                       name=_j(name, "attn"))
        x = x + ao
        hx = L.norm(p["normx"], x, cfg.norm)
        if cache is not None and cross_kv is not None:      # prefill: store
            nc = dict(nc or {}, ck=cross_kv[0].astype(jnp.bfloat16),
                      cv=cross_kv[1].astype(jnp.bfloat16))
        elif cache is not None:                              # decode: reuse
            cross_kv = (cache["ck"], cache["cv"])
            nc = dict(nc or {}, ck=cache["ck"], cv=cache["cv"])
        xo, _ = A.gqa(p["xattn"], hx, positions, _attn_cfg(cfg, cross=True),
                      kv_override=cross_kv, name=_j(name, "xattn"))
        x = x + xo
        x = x + L.ffn(p["ffn"], L.norm(p["norm2"], x, cfg.norm), cfg.act,
                      _j(name, "ffn"))
        return x, nc, aux
    if kind == "mamba":
        h = L.norm(p["norm1"], x, cfg.norm)
        mo, ns = S.mamba2(p["mamba"], h, _mamba_cfg(cfg), state=cache,
                          name=_j(name, "mamba"), length_mask=length_mask)
        return x + mo, ns, aux
    if kind == "mlstm":
        h = L.norm(p["norm1"], x, cfg.norm)
        mo, ns = S.mlstm(p["core"], h, _xlstm_cfg(cfg), state=cache,
                         name=_j(name, "core"), length_mask=length_mask)
        return x + mo, ns, aux
    if kind == "slstm":
        h = L.norm(p["norm1"], x, cfg.norm)
        mo, ns = S.slstm(p["core"], h, _xlstm_cfg(cfg), state=cache,
                         name=_j(name, "core"), length_mask=length_mask)
        return x + mo, ns, aux
    if kind == "shared_attn":
        h = L.norm(p["norm1"], x, cfg.norm)
        ao, nc = A.gqa(shared["attn"], h, positions, _attn_cfg(cfg),
                       cache=cache, cache_index=cache_index, chunked=chunked,
                       pages=pages, write_mask=length_mask,
                       name="shared/attn")
        x = x + ao
        x = x + L.ffn(shared["ffn"],
                      L.norm(shared["norm2"], x, cfg.norm), cfg.act,
                      "shared/ffn")
        return x, nc, aux
    if kind == "hybrid":
        sb, sname = shared[block], f"shared/{block}"
        kv = state = nc = None
        if cache is not None:
            kv = {"k": cache["k"], "v": cache["v"]}
            state = {"ssm": cache["ssm"], "conv": cache["conv"]}
        with jax.named_scope("shared_block"):
            h = L.norm(sb["norm1"], jnp.concatenate([x, emb], axis=-1),
                       cfg.norm)
            ao, nkv = A.gqa(sb["attn"], h, positions, _attn_cfg(cfg),
                            cache=kv, cache_index=cache_index,
                            chunked=chunked, pages=pages,
                            write_mask=length_mask, name=_j(sname, "attn"))
            h = L.norm(sb["norm2"], ao, cfg.norm)
            lora = L.dense(p["lora_b"],
                           L.dense(p["lora_a"], h, _j(name, "lora_a")),
                           _j(name, "lora_b"))
            gate, up = jnp.split(
                L.dense(sb["ffn"]["gate_up"], h, _j(sname, "ffn/gate_up"))
                + lora, 2, axis=-1)
            y = L.dense(sb["ffn"]["down"], L.act_fn(cfg.act)(gate) * up,
                        _j(sname, "ffn/down"))
        y = L.dense(p["linear"], y, _j(name, "linear"))
        with jax.named_scope("mixer"):
            mo, ns = S.mamba2(p["mamba"], L.norm(p["norm1"], x + y, cfg.norm),
                              _mamba_cfg(cfg), state=state,
                              name=_j(name, "mamba"),
                              length_mask=length_mask)
        if cache is not None:
            nc = {**nkv, **ns}
        return x + mo, nc, aux
    raise ValueError(kind)


# --------------------------------------------------------------- model init

def _zamba_remainder(cfg: ArchConfig) -> int:
    period = len(cfg.pattern)
    return cfg.n_layers - (cfg.n_layers // period) * period


def init_lm(key, cfg: ArchConfig):
    dt = _dtype(cfg)
    keys = jax.random.split(key, 16)
    params: dict = {
        "emb": (jax.random.normal(keys[0], (cfg.vocab, cfg.d_model)) * 0.02
                ).astype(dt),
        "final_norm": L.init_norm(cfg.d_model, dt, cfg.norm),
    }
    if not cfg.tie_embeddings:
        params["head"] = L.init_dense(keys[1], cfg.d_model, cfg.vocab, dt,
                                      scale=cfg.d_model ** -0.5)

    period = len(cfg.pattern)
    repeats = cfg.n_layers // period
    if cfg.moe_first_dense and period == 1:
        repeats -= 1  # layer 0 lives in params["first_dense"], unscanned

    def init_unit(k):
        uks = jax.random.split(k, period)
        return tuple(init_block(uks[i], cfg, cfg.pattern[i], layer_idx=1)
                     for i in range(period))

    unit_keys = jax.random.split(keys[2], repeats)
    params["units"] = jax.vmap(init_unit)(unit_keys)

    rem = _zamba_remainder(cfg)
    if rem:
        rks = jax.random.split(keys[3], rem)
        params["rem"] = [init_block(rks[i], cfg, cfg.pattern[i % period])
                         for i in range(rem)]
    if "shared_attn" in cfg.pattern:
        params["shared"] = init_shared_block(keys[4], cfg)
    if "hybrid" in cfg.pattern:
        hybrid_blocks(cfg)          # refuses blocks that change per repeat
        params["shared"] = [init_hybrid_block(k, cfg) for k in
                            jax.random.split(keys[4], cfg.mem_blocks)]
    if cfg.moe_first_dense:
        # deepseek: layer 0 replaced by a dense-FFN copy, unscanned
        params["first_dense"] = init_block(keys[5], cfg, cfg.pattern[0],
                                           layer_idx=0)
    if cfg.encoder_layers:
        enc_keys = jax.random.split(keys[6], cfg.encoder_layers)
        enc_cfg = dataclasses.replace(cfg, moe=None, parallel_block=False,
                                      pattern=("attn",))
        params["enc_units"] = jax.vmap(
            lambda k: (init_block(k, enc_cfg, "attn"),))(enc_keys)
        params["enc_norm"] = L.init_norm(cfg.d_model, dt, cfg.norm)
    return params


# ------------------------------------------------------------- cross kv prep

def _frontend_kv(params_attn, cross_source, cfg: ArchConfig, name=None):
    """Project frontend embeddings to (k, v) for cross-attention."""
    B, T, _ = cross_source.shape
    KVH, hd = cfg.n_kv_heads, cfg.hd
    k = L.dense(params_attn["wk"], cross_source,
                _j(name, "wk")).reshape(B, T, KVH, hd)
    v = L.dense(params_attn["wv"], cross_source,
                _j(name, "wv")).reshape(B, T, KVH, hd)
    return k, v


def encode(params, cfg: ArchConfig, frames):
    """seamless encoder: frames (B, T, D) -> memory (B, T, D)."""
    enc_cfg = dataclasses.replace(cfg, moe=None, parallel_block=False)
    positions = jnp.arange(frames.shape[1])[None, :]

    def body(x, xs):
        ridx, unit = xs
        (blk,) = unit
        with _backend.scan_slot(ridx):
            h = L.norm(blk["norm1"], x, cfg.norm)
            acfg = dataclasses.replace(_attn_cfg(enc_cfg), causal=False)
            ao, _ = A.gqa(blk["attn"], h, positions, acfg,
                          name="enc_units/0/attn")
            x = x + ao
            x = x + L.ffn(blk["ffn"], L.norm(blk["norm2"], x, cfg.norm),
                          cfg.act, "enc_units/0/ffn")
        return x, None

    enc_repeats = jax.tree.leaves(params["enc_units"])[0].shape[0]
    x, _ = jax.lax.scan(body, frames,
                        (jnp.arange(enc_repeats), params["enc_units"]))
    return L.norm(params["enc_norm"], x, cfg.norm)


# --------------------------------------------------------------- full stack

def backbone(params, cfg: ArchConfig, x, positions, *, caches=None,
             cache_index=None, cross_source=None, chunked=False,
             remat=False, length_mask=None, pages=None):
    """Run all layers. caches: None or pytree matching cache_specs.
    Returns (hidden, new_caches, aux).

    ``cache_index`` scalar or (B,) per-slot positions, ``length_mask``
    (B, S) valid-token mask, ``pages`` (B, W) paged-KV page table — see
    `block_apply`."""
    from repro.distributed.sharding import constrain
    period = len(cfg.pattern)
    shared = params.get("shared")
    blocks = hybrid_blocks(cfg) if "hybrid" in cfg.pattern else {}
    emb = x

    def unit_fn(carry, xs):
        x, aux = carry
        ridx, unit_params, unit_cache = xs
        new_cache = []
        # publish the (traced) repeat index: scan-stacked layers are named by
        # their base path (e.g. "units/0/mamba/in_proj") and a name-keyed
        # backend selects the repeat's prepared kernels with this index
        with _backend.scan_slot(ridx):
            for i, kind in enumerate(cfg.pattern):
                blk = unit_params[i]
                c = unit_cache[i] if unit_cache is not None else None
                ckv = None
                if kind in ("cross", "dec") and cross_source is not None:
                    att = blk["attn"] if kind == "cross" else blk["xattn"]
                    sub = "attn" if kind == "cross" else "xattn"
                    ckv = _frontend_kv(att, cross_source, cfg,
                                       name=f"units/{i}/{sub}")
                x, nc, a = block_apply(
                    blk, x, kind, cfg, positions, cache=c,
                    cache_index=cache_index, cross_kv=ckv, chunked=chunked,
                    shared=shared, name=f"units/{i}",
                    length_mask=length_mask, pages=pages, emb=emb,
                    block=blocks.get(("units", i)))
                aux = aux + a
                new_cache.append(nc)
        x = constrain(x, "act")
        return (x, aux), tuple(new_cache)

    unit_caches = caches["units"] if caches is not None else None
    if params.get("first_dense") is not None:
        fd_cache = caches["first"] if caches is not None else None
        x, nfc, a0 = block_apply(params["first_dense"], x, cfg.pattern[0], cfg,
                                 positions, cache=fd_cache,
                                 cache_index=cache_index, chunked=chunked,
                                 shared=shared, name="first_dense",
                                 length_mask=length_mask, pages=pages)
        units = params["units"]  # init_lm already excluded layer 0
    else:
        x, nfc, a0 = x, None, 0.0
        units = params["units"]

    repeats = jax.tree.leaves(units)[0].shape[0]
    xs = (jnp.arange(repeats), units, unit_caches)
    body = jax.checkpoint(unit_fn, prevent_cse=False) if remat else unit_fn
    (x, aux), new_unit_caches = jax.lax.scan(body, (x, a0), xs)

    new_rem = []
    if params.get("rem"):
        rem_caches = caches["rem"] if caches is not None else None
        for i, blk in enumerate(params["rem"]):
            kind = cfg.pattern[i % period]
            c = rem_caches[i] if rem_caches is not None else None
            x, nc, a = block_apply(blk, x, kind, cfg, positions, cache=c,
                                   cache_index=cache_index, chunked=chunked,
                                   shared=shared, name=f"rem/{i}",
                                   length_mask=length_mask, pages=pages,
                                   emb=emb, block=blocks.get(("rem", i)))
            aux = aux + a
            new_rem.append(nc)

    x = L.norm(params["final_norm"], x, cfg.norm)
    new_caches = None
    if caches is not None:
        new_caches = {"units": new_unit_caches}
        if params.get("rem"):
            new_caches["rem"] = new_rem
        if nfc is not None:
            new_caches["first"] = nfc
    return x, new_caches, aux


def _head_weight(params, cfg: ArchConfig):
    if cfg.tie_embeddings:
        return params["emb"].T
    h = params["head"]
    if "w_q" in h:  # int8 serve domain
        return h["w_q"].astype(params["emb"].dtype) * \
            h["w_s"].astype(params["emb"].dtype)[..., None, :]
    return h["w"]


def _project_logits(params, cfg: ArchConfig, h):
    """Vocab projection of the last hidden states, routed through the
    pluggable matmul backend when one is installed (per-layer planned
    execution of the head; see repro.models._backend)."""
    be = _backend.current()
    if be is not None and not cfg.tie_embeddings and "head" in params:
        y = be("head", params["head"], h)
        if y is not None:
            return y.astype(jnp.float32)
    return (h @ _head_weight(params, cfg)).astype(jnp.float32)


def chunked_ce(h, w, targets, chunk=512):
    """Cross-entropy with the vocab projection computed per sequence chunk
    (rematerialized in backward) — avoids materializing (B,S,V) logits."""
    B, Sq, D = h.shape
    chunk = min(chunk, Sq)
    n = Sq // chunk
    hc = h[:, :n * chunk].reshape(B, n, chunk, D).transpose(1, 0, 2, 3)
    tc = targets[:, :n * chunk].reshape(B, n, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def one(hb, tb):
        logits = (hb @ w).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, tb[..., None], axis=-1)[..., 0]

    def body(acc, xs):
        hb, tb = xs
        return acc + jnp.sum(one(hb, tb)), None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, tc))
    return tot / (B * n * chunk)


def lm_loss(params, cfg: ArchConfig, batch, remat=False):
    """batch: {"tokens","targets"[, "frontend"]} -> scalar loss."""
    tokens = batch["tokens"]
    B, Sq = tokens.shape
    x = params["emb"][tokens]
    positions = jnp.arange(Sq)[None, :]
    cross_source = None
    if cfg.frontend == "vision":
        cross_source = batch["frontend"]
    elif cfg.frontend == "audio":
        cross_source = encode(params, cfg, batch["frontend"])
    h, _, aux = backbone(params, cfg, x, positions,
                         cross_source=cross_source, chunked=Sq > 2048,
                         remat=remat)
    loss = chunked_ce(h, _head_weight(params, cfg), batch["targets"])
    return loss + 0.01 * aux


# --------------------------------------------------------------- serving

def _block_cache_spec(cfg: ArchConfig, kind: str, B: int, S_max: int):
    dt = jnp.int8 if cfg.kv_cache_dtype == "int8" else jnp.bfloat16
    if kind == "hybrid":
        return {**_block_cache_spec(cfg, "attn", B, S_max),
                **_block_cache_spec(cfg, "mamba", B, S_max)}
    if kind in ("attn", "dec", "shared_attn"):
        spec = {"k": ((B, S_max, cfg.n_kv_heads, cfg.hd), dt),
                "v": ((B, S_max, cfg.n_kv_heads, cfg.hd), dt)}
        if kind == "dec":
            # encoder memory KV, computed once at prefill (decode reuses)
            spec["ck"] = ((B, cfg.frontend_tokens, cfg.n_kv_heads, cfg.hd), dt)
            spec["cv"] = ((B, cfg.frontend_tokens, cfg.n_kv_heads, cfg.hd), dt)
        return spec
    if kind == "mla":
        m = cfg.mla
        return {"latent": ((B, S_max, m.kv_lora_rank + m.qk_rope_dim), dt)}
    if kind == "cross":
        return {"ck": ((B, cfg.frontend_tokens, cfg.n_kv_heads, cfg.hd), dt),
                "cv": ((B, cfg.frontend_tokens, cfg.n_kv_heads, cfg.hd), dt)}
    if kind == "mamba":
        mc = _mamba_cfg(cfg)
        # the convolution window holds the in_proj output as computed,
        # whatever the KV cache's dtype
        return {"ssm": ((B, mc.n_heads, mc.head_dim, mc.d_state), jnp.float32),
                "conv": ((B, mc.conv_kernel - 1, mc.conv_dim), _dtype(cfg))}
    if kind == "mlstm":
        xc = _xlstm_cfg(cfg)
        return {"C": ((B, xc.n_heads, xc.head_dim, xc.head_dim), jnp.float32),
                "n": ((B, xc.n_heads, xc.head_dim), jnp.float32),
                "m": ((B, xc.n_heads), jnp.float32),
                "conv": ((B, xc.conv_kernel - 1, xc.d_inner), dt)}
    if kind == "slstm":
        xc = _xlstm_cfg(cfg)
        z = lambda *s: (s, jnp.float32)
        return {"c": z(B, xc.n_heads, xc.head_dim),
                "n": z(B, xc.n_heads, xc.head_dim),
                "h": z(B, xc.n_heads, xc.head_dim),
                "m": z(B, xc.n_heads)}
    raise ValueError(kind)


def _materialize(spec, make):
    if spec is None:
        return None
    if isinstance(spec, dict):
        return {k: _materialize(v, make) for k, v in spec.items()}
    shape, dt = spec
    return make(shape, dt)


def _assemble_caches(cfg: ArchConfig, block_fn, stacked, plain):
    """Shared layout walk for every cache-shaped pytree: ``block_fn(kind)``
    emits one block's leaf dict; ``stacked(leaf, repeats)`` builds the
    scan-stacked "units" version of a leaf, ``plain(leaf)`` the unstacked
    "rem"/"first" version."""
    period = len(cfg.pattern)
    repeats = cfg.n_layers // period
    if cfg.moe_first_dense and period == 1:
        repeats -= 1  # layer 0 cache lives under "first"

    def walk(spec, leaf):
        if spec is None:
            return None
        if isinstance(spec, dict):
            return {k: walk(v, leaf) for k, v in spec.items()}
        return leaf(spec)

    caches = {"units": tuple(
        walk(block_fn(kind), lambda sp: stacked(sp, repeats))
        for kind in cfg.pattern)}
    rem = _zamba_remainder(cfg)
    if rem:
        caches["rem"] = [walk(block_fn(cfg.pattern[i % period]), plain)
                         for i in range(rem)]
    if cfg.moe_first_dense:
        caches["first"] = walk(block_fn(cfg.pattern[0]), plain)
    return caches


def cache_specs(cfg: ArchConfig, B: int, S_max: int, concrete=False):
    make = (lambda s, d: jnp.zeros(s, d)) if concrete else \
        (lambda s, d: jax.ShapeDtypeStruct(s, d))
    return _assemble_caches(
        cfg, lambda kind: _block_cache_spec(cfg, kind, B, S_max),
        stacked=lambda sp, r: make((r, *sp[0]), sp[1]),
        plain=lambda sp: make(*sp))


def init_cache(cfg: ArchConfig, B: int, S_max: int):
    return cache_specs(cfg, B, S_max, concrete=True)


# ------------------------------------------------------------ paged KV pool

# Sequence-indexed attention-KV leaves — the ones a paged layout moves from
# per-slot (B, S_max, ...) buffers into the shared page pool.  Everything
# else (recurrent state, conv tails, encoder memory) is O(1)-per-slot and
# stays slot-indexed in both layouts.
_PAGED_KEYS = frozenset({"k", "v", "latent"})
_SEQ_KINDS = frozenset({"attn", "dec", "shared_attn", "mla", "hybrid"})


def _paged_block_cache_spec(cfg: ArchConfig, kind: str, B: int,
                            pool_rows: int, page_size: int):
    spec = _block_cache_spec(cfg, kind, B, 1)
    if kind not in _SEQ_KINDS:
        return spec
    for key in spec:
        if key in _PAGED_KEYS:
            (_, _, *feat), dt = spec[key]
            spec[key] = ((pool_rows, page_size, *feat), dt)
    return spec


def paged_cache_specs(cfg: ArchConfig, B: int, pool_rows: int,
                      page_size: int, concrete=False):
    """Cache pytree for the PAGED layout: attention-KV leaves become the
    shared ``(pool_rows, page_size, ...)`` page pool (``pool_rows`` includes
    the trash row 0 — pass num_pages + 1); per-slot state keeps its dense
    shape.  Same tree structure as `cache_specs`."""
    make = (lambda s, d: jnp.zeros(s, d)) if concrete else \
        (lambda s, d: jax.ShapeDtypeStruct(s, d))
    return _assemble_caches(
        cfg, lambda kind: _paged_block_cache_spec(cfg, kind, B, pool_rows,
                                                  page_size),
        stacked=lambda sp, r: make((r, *sp[0]), sp[1]),
        plain=lambda sp: make(*sp))


def init_paged_cache(cfg: ArchConfig, B: int, pool_rows: int, page_size: int):
    return paged_cache_specs(cfg, B, pool_rows, page_size, concrete=True)


def cache_kv_axes(cfg: ArchConfig):
    """Marker pytree (same structure as `cache_specs`/`paged_cache_specs`):
    ``"page"`` for sequence-indexed attention-KV leaves, ``"slot"`` for
    per-slot state, with the count of leading scan-stack axes appended —
    ``"page1"`` means "KV leaf whose pool/batch axis sits at axis 1 under
    the stacked repeats".  This is what the engine's jitted slot-reset /
    page-copy helpers and the KV-byte accounting use to address leaves of
    either layout."""
    def roles(kind):
        spec = _block_cache_spec(cfg, kind, 1, 1)
        return {key: ("page" if kind in _SEQ_KINDS and key in _PAGED_KEYS
                      else "slot") for key in spec}
    return _assemble_caches(cfg, roles,
                            stacked=lambda role, r: role + "1",
                            plain=lambda role: role + "0")


def cache_batch_axes(caches):
    """Pytree (matching ``caches``) of each leaf's BATCH axis: ``"units"``
    leaves are scan-stacked with a leading repeats axis (batch is axis 1),
    everything else carries batch first.  This is the layout knowledge
    `scatter_cache` needs to address slots."""
    axes = {"units": jax.tree.map(lambda _: 1, caches["units"])}
    for k in ("rem", "first"):
        if k in caches:
            axes[k] = jax.tree.map(lambda _: 0, caches[k])
    return axes


def scatter_cache(caches, sub, slots):
    """Write a k-request cache pytree ``sub`` into the B-slot pool
    ``caches`` at slot indices ``slots`` (k,) — the continuous-batching
    admission step: freshly prefilled per-request caches land in the slots
    the scheduler assigned, replacing whatever a retired request left
    there.  Jit-safe (``slots`` may be traced)."""
    slots = jnp.asarray(slots)

    def put(buf, s, axis):
        if axis == 0:
            return buf.at[slots].set(s.astype(buf.dtype))
        return buf.at[:, slots].set(s.astype(buf.dtype))

    return jax.tree.map(put, caches, sub, cache_batch_axes(caches))


def prefill(params, cfg: ArchConfig, tokens, caches, cross_source=None,
            lengths=None, variant=None):
    """Process the prompt, fill caches, return (last_logits, caches).

    ``lengths`` (B,) enables RAGGED prefill of right-padded prompts: valid
    tokens occupy positions ``[0, lengths[b])`` of each row.  Recurrent
    (SSM/xLSTM) states and MoE dispatch suppress the padded tail exactly;
    attention KV written at padded positions is garbage by contract — every
    subsequent read masks the cache by per-slot length (`decode_step` with
    a (B,) index).  The returned logits are taken at each slot's LAST VALID
    position (``lengths - 1``), not at the padded row end.

    ``variant`` selects the plan variant of a multi-plan backend
    (`repro.runtime.PlanSet`) for this call — a STATIC string (make it a
    ``static_argnames`` entry when jitting); None keeps any surrounding
    ``plan_variant`` selection / the backend default."""
    with _backend.plan_variant(variant):
        return _prefill_body(params, cfg, tokens, caches, cross_source,
                             lengths)


def _prefill_body(params, cfg, tokens, caches, cross_source, lengths):
    B, Sq = tokens.shape
    x = params["emb"][tokens]
    positions = jnp.arange(Sq)[None, :]
    length_mask = None
    if lengths is not None:
        length_mask = jnp.arange(Sq)[None, :] < jnp.asarray(lengths)[:, None]
    if cfg.frontend == "audio" and cross_source is not None:
        cross_source = encode(params, cfg, cross_source)
    h, caches, _ = backbone(params, cfg, x, positions, caches=caches,
                            cache_index=0, cross_source=cross_source,
                            chunked=Sq > 2048, length_mask=length_mask)
    h_last = (h[:, -1] if lengths is None
              else jnp.take_along_axis(
                  h, (jnp.asarray(lengths) - 1)[:, None, None], axis=1)[:, 0])
    logits = _project_logits(params, cfg, h_last)
    return logits, caches


def prefill_chunk(params, cfg: ArchConfig, tokens, caches, index, valid,
                  pages, cross_source=None, variant=None,
                  full_logits: bool = False):
    """One fixed-size chunk of a paged CHUNKED prefill.

    ``tokens`` (B, C) holds the next (up to C) prompt tokens of every
    currently-prefilling slot, left-aligned; ``index`` (B,) is each slot's
    prefill progress (tokens already in its pages) and ``valid`` (B,) how
    many of this chunk's tokens are real — 0 for slots that are decoding or
    idle, whose rows are fully masked: attention writes land in the trash
    page and recurrent state carries through unchanged (`ssm` masked steps
    are exact identities), so interleaving chunks with decode steps cannot
    perturb other slots.  Recurrent state accumulated in ``caches`` across
    calls IS the carried chunk boundary state.  Returns (logits at each
    slot's last valid token — the slot's first generated token once its
    whole prompt is in, garbage before that — and the updated caches).

    ``variant`` selects the plan variant of a multi-plan backend (STATIC —
    see `prefill`).  ``full_logits=True`` returns logits at EVERY chunk
    position ``(B, C, V)`` instead of the last valid one — the speculative
    verify step reads the target model's prediction after each drafted
    token from one chunk call (rows past ``valid`` are garbage by the same
    masking contract)."""
    with _backend.plan_variant(variant):
        B, C = tokens.shape
        index = jnp.asarray(index)
        valid = jnp.asarray(valid)
        x = params["emb"][tokens]
        positions = index[:, None] + jnp.arange(C)[None, :]
        length_mask = jnp.arange(C)[None, :] < valid[:, None]
        if cfg.frontend == "audio" and cross_source is not None:
            cross_source = encode(params, cfg, cross_source)
        h, caches, _ = backbone(params, cfg, x, positions, caches=caches,
                                cache_index=index, cross_source=cross_source,
                                length_mask=length_mask, pages=pages)
        if full_logits:
            return _project_logits(params, cfg, h), caches
        last = jnp.clip(valid - 1, 0, C - 1)
        h_last = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
        logits = _project_logits(params, cfg, h_last)
        return logits, caches


def decode_step(params, cfg: ArchConfig, token, caches, index,
                cross_source=None, active=None, pages=None, variant=None):
    """One decode step. token (B,), index: position of the new token — a
    scalar (classic same-length batch) or a ``(B,)`` array of PER-SLOT cache
    lengths (continuous batching: each slot's token lands at that slot's own
    position and attention masks the cache per slot).  ``active`` (B,) bool
    marks live slots: retired/empty slots are suppressed in cross-slot
    coupling (MoE capacity) and their recurrent states carry through
    unchanged — their logits are garbage by contract.  ``pages`` (B, W)
    switches attention KV to the paged pool layout (see `block_apply`).
    Cross-attention KV (frontend/encoder memory) is read from the cache
    written at prefill — cross_source is ignored here.  ``variant`` selects
    the plan variant of a multi-plan backend (STATIC — see `prefill`)."""
    with _backend.plan_variant(variant):
        x = params["emb"][token][:, None, :]
        B = x.shape[0]
        positions = (jnp.asarray(index)[:, None] if jnp.ndim(index) == 1
                     else jnp.full((B, 1), index))
        length_mask = None if active is None else jnp.asarray(active)[:, None]
        h, caches, _ = backbone(params, cfg, x, positions, caches=caches,
                                cache_index=index, cross_source=None,
                                length_mask=length_mask, pages=pages)
        logits = _project_logits(params, cfg, h[:, -1])
        return logits, caches


# ------------------------------------------------- serve-time quantization

def quantize_for_serve(params, cfg: ArchConfig):
    """Replace projection weights with int8 codes + per-out-channel scales
    (the TPU int8 precision domain of DESIGN.md §2).  Embedding, norms and
    small vectors stay bf16.  Works on concrete params or on
    ShapeDtypeStructs (for the dry-run)."""
    if cfg.serve_weight_dtype != "int8":
        return params

    def walk(node):
        if isinstance(node, dict):
            if "w" in node and hasattr(node["w"], "ndim") and \
                    node["w"].ndim >= 2:
                w = node["w"]
                rest = {k: walk(v) for k, v in node.items() if k != "w"}
                # per-out-channel scale; stacked scan params (R, in, out)
                # keep their leading axes: scale shape = (*lead, out)
                s_shape = w.shape[:-2] + (w.shape[-1],)
                if isinstance(w, jax.ShapeDtypeStruct):
                    rest["w_q"] = jax.ShapeDtypeStruct(w.shape, jnp.int8)
                    rest["w_s"] = jax.ShapeDtypeStruct(s_shape, jnp.float32)
                else:
                    s_ = jnp.maximum(jnp.max(jnp.abs(w.astype(jnp.float32)),
                                             axis=-2), 1e-8) / 127.0
                    rest["w_q"] = jnp.clip(
                        jnp.round(w.astype(jnp.float32) / s_[..., None, :]),
                        -127, 127).astype(jnp.int8)
                    rest["w_s"] = s_.astype(jnp.float32)
                return rest
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v) for v in node]
        if isinstance(node, tuple):
            return tuple(walk(v) for v in node)
        return node

    # keep the embedding (and tied head) in bf16: vocab-gather accuracy
    out = walk({k: v for k, v in params.items() if k != "emb"})
    out["emb"] = params["emb"]
    return out
