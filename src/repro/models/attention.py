"""Attention variants: GQA (optional sliding window), cross-attention, MLA.

Shapes: hidden (B, S, D); q (B, S, H, hd); kv (B, S, KVH, hd).
GQA is computed grouped — q reshaped to (B, S, KVH, G, hd) — so KV heads are
never materialized H times.  Long sequences use a double-chunked online-
softmax attention (the jnp reference of the Pallas flash kernel in
``repro.kernels.flash_attention``).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models._backend import join as _j

# int8 KV-cache quantization step (post-norm k/v live in ~[-8, 8])
KV_QSCALE = 16.0


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    sliding_window: int | None = None
    bias: bool = False
    causal: bool = True
    rotary: bool = True
    scale: float | None = None      # softmax scale; None: head_dim ** -0.5


def init_attn(key, cfg: AttnConfig, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 4)
    H, KVH, hd, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    return {
        "wq": L.init_dense(ks[0], d, H * hd, dtype, bias=cfg.bias),
        "wk": L.init_dense(ks[1], d, KVH * hd, dtype, bias=cfg.bias),
        "wv": L.init_dense(ks[2], d, KVH * hd, dtype, bias=cfg.bias),
        "wo": L.init_dense(ks[3], H * hd, d, dtype,
                           scale=(H * hd) ** -0.5, bias=cfg.bias),
    }


# ---------------------------------------------------------------- core math

def _grouped_scores_softmax_out(q, k, v, mask, scale):
    """q (B,Sq,KVH,G,hd); k,v (B,Sk,KVH,hd); mask (Sq,Sk) or (B,Sq,Sk) bool
    or None (the batched form carries per-slot cache lengths)."""
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32) * scale
    if mask is not None:
        if mask.ndim == 3:                  # per-slot: (B,Sq,Sk) over
            mask = mask[:, None, None]      # s (B,KVH,G,Sq,Sk)
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v)


def full_attention(q, k, v, *, causal, window=None, q_pos0=0, kv_len=None,
                   scale=None):
    """Unchunked reference path (small S / decode).

    ``q_pos0`` and ``kv_len`` may be scalars (one position for the whole
    batch — the classic path) or ``(B,)`` arrays of PER-SLOT positions /
    cache lengths (the continuous-batching decode path: every slot sits at
    its own sequence length, so the causal/window/length masks must be
    built per slot)."""
    B, Sq, KVH, G, hd = q.shape
    Sk = k.shape[1]
    scale = hd ** -0.5 if scale is None else scale
    mask = None
    per_slot = jnp.ndim(q_pos0) == 1 or jnp.ndim(kv_len) == 1
    if per_slot:                            # (B,Sq,Sk)-shaped index grids
        q0 = jnp.reshape(jnp.asarray(q_pos0), (-1, 1, 1)) \
            if jnp.ndim(q_pos0) == 1 else q_pos0
        qi = q0 + jnp.arange(Sq)[None, :, None]
        ki = jnp.arange(Sk)[None, None, :]
    else:                                   # (Sq,Sk) grids, broadcast over B
        qi = q_pos0 + jnp.arange(Sq)[:, None]
        ki = jnp.arange(Sk)[None, :]
    if causal:
        mask = ki <= qi
    if window is not None:
        wm = ki > qi - window
        mask = wm if mask is None else (mask & wm)
    if kv_len is not None:
        kl = (jnp.reshape(jnp.asarray(kv_len), (-1, 1, 1))
              if jnp.ndim(kv_len) == 1 else kv_len)
        lm = ki < kl
        mask = lm if mask is None else (mask & lm)
    return _grouped_scores_softmax_out(q, k, v, mask, scale)


def chunked_attention(q, k, v, *, causal=True, window=None,
                      q_chunk=512, k_chunk=1024, kv_len=None, scale=None):
    """Double-chunked online-softmax attention (flash-style, pure jnp).

    Memory per step is O(q_chunk * k_chunk); the causal upper triangle is
    masked (not skipped) to keep scan trip counts static.
    """
    B, Sq, KVH, G, hd = q.shape
    Sk, vd = k.shape[1], v.shape[-1]
    q_chunk = min(q_chunk, Sq)
    k_chunk = min(k_chunk, Sk)
    assert Sq % q_chunk == 0 and Sk % k_chunk == 0
    nq, nk = Sq // q_chunk, Sk // k_chunk
    scale = hd ** -0.5 if scale is None else scale

    qc = q.reshape(B, nq, q_chunk, KVH, G, hd).transpose(1, 0, 2, 3, 4, 5)
    kc = k.reshape(B, nk, k_chunk, KVH, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, k_chunk, KVH, vd).transpose(1, 0, 2, 3, 4)

    import jax as _jax

    @_jax.checkpoint
    def q_body(_, qi_blk):
        qi, qb = qi_blk  # index, (B, qc, KVH, G, hd)
        m0 = jnp.full((B, KVH, G, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, KVH, G, q_chunk), jnp.float32)
        o0 = jnp.zeros((B, q_chunk, KVH, G, vd), jnp.float32)

        @_jax.checkpoint
        def k_body(carry, ki_blk):
            m, l, o = carry
            ki, kb, vb = ki_blk
            s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb).astype(jnp.float32) * scale
            qpos = qi * q_chunk + jnp.arange(q_chunk)[:, None]
            kpos = ki * k_chunk + jnp.arange(k_chunk)[None, :]
            mask = jnp.ones((q_chunk, k_chunk), bool)
            if causal:
                mask &= kpos <= qpos
            if window is not None:
                mask &= kpos > qpos - window
            if kv_len is not None:
                mask = mask & (kpos < kv_len)
            s = jnp.where(mask, s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(vb.dtype), vb)
            o_new = o * alpha.transpose(0, 3, 1, 2)[..., None] + pv.astype(jnp.float32)
            return (m_new, l_new, o_new), None

        (m, l, o), _ = jax.lax.scan(
            k_body, (m0, l0, o0), (jnp.arange(nk), kc, vc))
        o = o / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
        return None, o.astype(q.dtype)

    _, out = jax.lax.scan(q_body, None, (jnp.arange(nq), qc))
    # out: (nq, B, q_chunk, KVH, G, vd) -> (B, Sq, KVH, G, vd)
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, KVH, G, vd)


def cache_update(buf, val, index):
    """Write ``val (B, S, ...)`` into ``buf (B, S_max, ...)`` starting at
    sequence position ``index`` — a scalar (whole batch at one position) or
    a ``(B,)`` array of per-slot positions (continuous batching: each slot's
    KV lands at that slot's own cache length)."""
    if jnp.ndim(index) == 1:
        def one(b, v, i):
            start = (i,) + (0,) * (b.ndim - 1)
            return jax.lax.dynamic_update_slice(b, v, start)
        return jax.vmap(one)(buf, val.astype(buf.dtype), index)
    start = (0, index) + (0,) * (buf.ndim - 2)
    return jax.lax.dynamic_update_slice(buf, val.astype(buf.dtype), start)


# ------------------------------------------------------------- paged KV pool

def _paged_rows(pool, val, pages, index, mask):
    """Flat row indices of ``val (B, S, ...)`` in ``pool (P, ps, ...)``.

    ``pages`` is the (B, W) int32 page table; ``index`` the (B,)-or-scalar
    starting logical position.  Row 0 of the pool is the TRASH page: masked
    (padding) writes and any position whose page-table entry is 0 land
    there, so a slot with a zeroed table can never corrupt live pages."""
    B, S = val.shape[:2]
    W, ps = pages.shape[1], pool.shape[1]
    pos = jnp.reshape(jnp.asarray(index), (-1, 1)) + jnp.arange(S)[None, :]
    logical = jnp.clip(pos // ps, 0, W - 1)
    phys = jnp.take_along_axis(pages, logical, axis=1) * ps + pos % ps
    valid = pos < W * ps
    if mask is not None:
        valid = valid & mask
    return jnp.where(valid, phys, 0)


def paged_update(pool, val, pages, index, mask=None):
    """Scatter ``val (B, S, ...)`` into the shared page pool ``pool
    (P, ps, ...)`` at per-slot logical positions ``index`` under page table
    ``pages (B, W)``; ``mask (B, S)`` suppresses padding writes (they hit
    the trash page, row 0)."""
    rows = _paged_rows(pool, val, pages, index, mask)
    B, S = val.shape[:2]
    feat = pool.shape[2:]
    flat = pool.reshape((pool.shape[0] * pool.shape[1],) + feat)
    flat = flat.at[rows.reshape(-1)].set(
        val.astype(pool.dtype).reshape((B * S,) + feat))
    return flat.reshape(pool.shape)


def paged_gather(pool, pages):
    """Gather each slot's pages into a dense (B, W*ps, ...) sequence view —
    `full_attention`'s q_pos0/kv_len masking then applies unchanged (the
    tail beyond kv_len, including any trash-page rows, is masked out)."""
    B, W = pages.shape
    ps = pool.shape[1]
    return pool[pages].reshape((B, W * ps) + pool.shape[2:])


# ---------------------------------------------------------------- GQA layer

def gqa(p, x, positions, cfg: AttnConfig, *, cache=None, cache_index=None,
        chunked=False, kv_override=None, pages=None, write_mask=None,
        name=None):
    """Grouped-query attention.

    cache: optional dict {"k","v"} of (B, S_max, KVH, hd) + writes at
    ``cache_index`` — a scalar, or a ``(B,)`` array of per-slot positions
    (the continuous-batching decode path; masks then build per slot);
    decode passes S==1 inputs.  kv_override supplies precomputed (k, v) for
    cross-attention.  With ``pages`` (a (B, W) page table) the cache leaves
    are the SHARED (num_pages, page_size, ...) pool instead: writes scatter
    through the table (`paged_update`, padding suppressed by ``write_mask``)
    and reads attend over the gathered per-slot view (`paged_gather`) under
    the same q_pos0/kv_len masks.  ``name``: this block's pytree path,
    threaded into the projections' matmul-backend calls.
    """
    B, S, D = x.shape
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // KVH
    q = L.dense(p["wq"], x, _j(name, "wq")).reshape(B, S, H, hd)
    if kv_override is None:
        k = L.dense(p["wk"], x, _j(name, "wk")).reshape(B, S, KVH, hd)
        v = L.dense(p["wv"], x, _j(name, "wv")).reshape(B, S, KVH, hd)
        if cfg.rotary:
            q = L.rope(q, positions, cfg.rope_theta)
            k = L.rope(k, positions, cfg.rope_theta)
    else:
        k, v = kv_override

    if pages is not None:
        upd = partial(paged_update, pages=pages, index=cache_index,
                      mask=write_mask)
        view = partial(paged_gather, pages=pages)
    else:
        upd = partial(cache_update, index=cache_index)
        view = lambda buf: buf

    kv_len = None
    if cache is not None:
        if cache["k"].dtype == jnp.int8:
            # int8 KV-cache domain: codes = round(x * KV_QSCALE); the cache
            # HBM stream halves vs bf16 (EXPERIMENTS.md §Perf)
            enc = lambda t: jnp.clip(jnp.round(t.astype(jnp.float32) *
                                               KV_QSCALE), -127, 127
                                     ).astype(jnp.int8)
            kc = upd(cache["k"], enc(k))
            vc = upd(cache["v"], enc(v))
            new_cache = {"k": kc, "v": vc}
            k = view(kc).astype(x.dtype) * (1.0 / KV_QSCALE)
            v = view(vc).astype(x.dtype) * (1.0 / KV_QSCALE)
        else:
            kc = upd(cache["k"], k)
            vc = upd(cache["v"], v)
            new_cache = {"k": kc, "v": vc}
            k, v = view(kc), view(vc)
        kv_len = cache_index + S
    else:
        new_cache = None

    qg = q.reshape(B, S, KVH, G, hd)
    if chunked and S > 1:
        # long prefill: chunked flash attention directly over the (updated)
        # cache buffers; cache-backed prefill starts at position 0
        out = chunked_attention(qg, k, v, causal=cfg.causal,
                                window=cfg.sliding_window, kv_len=kv_len,
                                scale=cfg.scale)
    else:
        q_pos0 = cache_index if cache is not None else 0
        out = full_attention(qg, k, v, causal=cfg.causal,
                             window=cfg.sliding_window,
                             q_pos0=q_pos0, kv_len=kv_len, scale=cfg.scale)
    out = out.reshape(B, S, H * hd)
    return L.dense(p["wo"], out, _j(name, "wo")), new_cache


# ---------------------------------------------------------------- MLA layer

@dataclasses.dataclass(frozen=True)
class MLAConfig:
    d_model: int
    n_heads: int
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0


def init_mla(key, cfg: MLAConfig, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 5)
    H = cfg.n_heads
    qd = cfg.qk_nope_dim + cfg.qk_rope_dim
    return {
        "wq": L.init_dense(ks[0], cfg.d_model, H * qd, dtype),
        "kv_a": L.init_dense(ks[1], cfg.d_model,
                             cfg.kv_lora_rank + cfg.qk_rope_dim, dtype),
        "kv_norm": L.init_norm(cfg.kv_lora_rank, dtype),
        "kv_b": L.init_dense(ks[2], cfg.kv_lora_rank,
                             H * (cfg.qk_nope_dim + cfg.v_head_dim), dtype),
        "wo": L.init_dense(ks[3], H * cfg.v_head_dim, cfg.d_model, dtype,
                           scale=(H * cfg.v_head_dim) ** -0.5),
    }


def mla(p, x, positions, cfg: MLAConfig, *, cache=None, cache_index=None,
        chunked=False, pages=None, write_mask=None, name=None):
    """Multi-head Latent Attention (DeepSeek-V2). Cache holds the compressed
    latent + shared rope key: (B, S_max, kv_lora_rank + qk_rope_dim) — or,
    with ``pages``, the shared (num_pages, page_size, r + rd) pool read
    through the per-slot page table (see `gqa`)."""
    B, S, D = x.shape
    H = cfg.n_heads
    nd, rd, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim

    q = L.dense(p["wq"], x, _j(name, "wq")).reshape(B, S, H, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = L.rope(q_rope, positions, cfg.rope_theta)

    kv = L.dense(p["kv_a"], x, _j(name, "kv_a"))
    latent, k_rope = kv[..., :cfg.kv_lora_rank], kv[..., cfg.kv_lora_rank:]
    latent = L.norm(p["kv_norm"], latent)
    k_rope = L.rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]

    if pages is not None:
        upd = partial(paged_update, pages=pages, index=cache_index,
                      mask=write_mask)
        view = partial(paged_gather, pages=pages)
    else:
        upd = partial(cache_update, index=cache_index)
        view = lambda buf: buf

    kv_len = None
    if cache is not None:
        packed = jnp.concatenate([latent, k_rope], axis=-1)
        if cache["latent"].dtype == jnp.int8:
            codes = jnp.clip(jnp.round(packed.astype(jnp.float32) *
                                       KV_QSCALE), -127, 127).astype(jnp.int8)
            buf = upd(cache["latent"], codes)
            new_cache = {"latent": buf}
            deq = view(buf).astype(x.dtype) * (1.0 / KV_QSCALE)
            latent = deq[..., :cfg.kv_lora_rank]
            k_rope = deq[..., cfg.kv_lora_rank:]
        else:
            buf = upd(cache["latent"], packed)
            new_cache = {"latent": buf}
            seq = view(buf)
            latent = seq[..., :cfg.kv_lora_rank]
            k_rope = seq[..., cfg.kv_lora_rank:]
        kv_len = cache_index + S
    else:
        new_cache = None

    if cache is not None and S == 1:
        # ABSORBED decode path (DeepSeek-V2 Appendix): fold kv_b's
        # up-projections into the query / output sides so attention runs
        # directly against the compressed latent cache — O(H*r) per token
        # instead of re-expanding K/V for the whole cache (~100x fewer
        # FLOPs at 32k context; see EXPERIMENTS.md §Perf).
        r = cfg.kv_lora_rank
        kvb_p = p["kv_b"]
        if "w_q" in kvb_p:  # int8 serve domain: dequant for the absorb fold
            w_kvb_flat = kvb_p["w_q"].astype(x.dtype) * \
                kvb_p["w_s"].astype(x.dtype)[..., None, :]
        else:
            w_kvb_flat = kvb_p["w"]
        w_kvb = w_kvb_flat.reshape(r, H, nd + vd)
        w_uk, w_uv = w_kvb[..., :nd], w_kvb[..., nd:]
        q_abs = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_uk)
        scale = (nd + rd) ** -0.5
        scores = (jnp.einsum("bqhr,bsr->bhqs", q_abs.astype(jnp.float32),
                             latent.astype(jnp.float32)) +
                  jnp.einsum("bqhd,bsd->bhqs", q_rope.astype(jnp.float32),
                             k_rope.astype(jnp.float32))) * scale
        kl = (jnp.reshape(jnp.asarray(kv_len), (-1, 1, 1, 1))
              if jnp.ndim(kv_len) == 1 else kv_len)
        mask = jnp.arange(latent.shape[1])[None, None, None, :] < kl
        scores = jnp.where(mask, scores, -1e30)
        pw = jax.nn.softmax(scores, axis=-1)
        o_lat = jnp.einsum("bhqs,bsr->bqhr", pw,
                           latent.astype(jnp.float32))
        out = jnp.einsum("bqhr,rhv->bqhv", o_lat.astype(x.dtype), w_uv)
        out = out.reshape(B, S, H * vd)
        return L.dense(p["wo"], out, _j(name, "wo")), new_cache

    kvb = L.dense(p["kv_b"], latent,
                  _j(name, "kv_b")).reshape(B, latent.shape[1], H, nd + vd)
    k_nope, v = kvb[..., :nd], kvb[..., nd:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (*k_nope.shape[:3], rd))], axis=-1)
    qfull = jnp.concatenate([q_nope, q_rope], axis=-1)

    # MLA is MHA (KVH == H, G == 1) on the concatenated features.
    qg = qfull.reshape(B, S, H, 1, nd + rd)
    if chunked and S > 1:
        out = chunked_attention(qg, k, v, causal=True, kv_len=kv_len)
    else:
        q_pos0 = cache_index if cache is not None else 0
        out = full_attention(qg, k, v, causal=True, q_pos0=q_pos0, kv_len=kv_len)
    out = out.reshape(B, S, H * vd)
    return L.dense(p["wo"], out, _j(name, "wo")), new_cache
