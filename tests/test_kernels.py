"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracle,
sweeping shapes and dtypes (hypothesis for the matmuls)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional test dep (requirements-dev.txt)
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.quant_matmul import quant_matmul
from repro.kernels.split_precision import split_precision_matmul
from repro.kernels.ternary_matmul import ternary_matmul


def _rand_int8(key, shape, lo=-127, hi=128):
    return jax.random.randint(key, shape, lo, hi, dtype=jnp.int8)


# ------------------------------------------------------------ quant_matmul
def _quant_operands(m, k, n, seed=None):
    key = jax.random.PRNGKey(m + k + n if seed is None else seed)
    xq = _rand_int8(key, (m, k))
    wq = _rand_int8(jax.random.fold_in(key, 1), (k, n))
    sx = jnp.asarray(0.013, jnp.float32)
    sw = jax.random.uniform(jax.random.fold_in(key, 2), (n,), jnp.float32)
    return xq, wq, sx, sw


@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (128, 512, 128, 128, 128, 512),
    (256, 1024, 256, 128, 128, 512),
    (8, 512, 128, 8, 128, 512),
    (128, 512, 384, 128, 128, 256),
    (64, 1024, 340, 64, 256, 1024),   # ragged last N block, full-K block
    (96, 512, 300, 32, 128, 512),     # ragged N, bm != bn
    (100, 256, 256, 32, 256, 128),    # ragged last M block, K in 2 blocks
])
def test_quant_matmul_blocks(m, k, n, bm, bn, bk):
    """Accumulation is exact in int32 and the scales apply per element at
    the flush, so every tiling gives the oracle's bits."""
    xq, wq, sx, sw = _quant_operands(m, k, n)
    out = quant_matmul(xq, wq, sx, sw, bm=bm, bn=bn, bk=bk, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.quant_matmul_ref(xq, wq, sx, sw)))


@pytest.mark.parametrize("m,k,n,blocks", [
    (48, 256, 1100, None),            # chosen: (48, 1024, 256), ragged N
    (1100, 256, 1100, None),          # chosen: (1024, 1024, 256), ragged M, N
    (16, 200, 300, None),             # chosen: K zero-pads to 256
    (16, 2048, 8512, (128, 128, 512)),  # the fixed tiling it replaced
    (40, 640, 340, (32, 256, 640)),   # explicit: bm != bn, full K, ragged
])
def test_quant_matmul_op_bit_equal(m, k, n, blocks):
    xq, wq, sx, sw = _quant_operands(m, k, n)
    kw = {} if blocks is None else dict(zip(("bm", "bn", "bk"), blocks))
    out = ops.quant_matmul_op(xq, wq, sx, sw, interpret=True, **kw)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.quant_matmul_ref(xq, wq, sx, sw)))


#: v5e: 393e12 int8 operations/s over 819e9 HBM bytes/s
INT8_RIDGE = 393e12 / 819e9


@pytest.mark.parametrize("m", [16, 4096])
@pytest.mark.parametrize("k,n", [(2048, 8512), (4096, 2048)])
def test_quant_matmul_blocks_chosen(m, k, n):
    """The chooser at the mamba2-1.3b chat cell's projections (decode
    M=16, chunk M=4096; in_proj and out_proj)."""
    from repro.kernels.quant_matmul import vmem_bytes, vmem_limit_bytes
    bm, bn, bk = ops.quant_matmul_blocks(m, k, n)
    assert bk == k                      # one K step per output tile
    assert vmem_bytes(bm, bn, bk) <= ops.QUANT_VMEM_BUDGET
    assert vmem_bytes(bm, bn, bk) < vmem_limit_bytes(bm, bn, bk)
    if m <= 32:                         # decode: one row block, wide columns
        assert bm == m and bn >= 1024
        assert -(-n // bn) <= 9         # a few grid steps stream w once
    else:                               # chunk: tiles over the ridge
        assert 2 * bm * bn / (bm + bn) >= INT8_RIDGE
        assert bm % 256 == 0 or 256 % bm == 0  # whole prefill slots


@pytest.mark.parametrize("tuning", [None, {"bm": 8, "bn": 128, "bk": 64}])
def test_quant_layer_blocks_tuning_wins(tuning):
    """An explicit `LayerPlan.tuning` reaches the kernel; an untuned
    quant_matmul layer takes the chooser's blocks per call shape.  Either
    way the layer gives the oracle's bits."""
    from repro.runtime import KERNEL_QUANT, LayerPlan, execute_layer, \
        prepare_layer
    m, k, n = 24, 128, 300
    rng = np.random.default_rng(5)
    lp = LayerPlan(name="proj", kernel=KERNEL_QUANT, c_in=k, c_out=n,
                   perm=np.arange(n), counts=[n], boundaries=[n],
                   aligned_boundaries=[n], w_log_scales=[0.0],
                   act_log_scale=1.0, tuning=tuning)
    prep = prepare_layer(lp, jnp.asarray(rng.normal(size=(k, n)) * 0.1,
                                         jnp.float32))
    x = jnp.asarray(rng.normal(size=(2, m // 2, k)), jnp.float32)
    y = execute_layer(prep, x, interpret=True)
    want = (8, 128, 64) if tuning else ops.quant_matmul_blocks(m, k, n)
    assert prep.blocks == (want if tuning else None)
    assert prep.chosen == {m: want}
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(execute_layer(prep, x, reference=True)))


@settings(max_examples=8, deadline=None)
@given(m=st.sampled_from([16, 100, 128]), k=st.sampled_from([96, 512]),
       n=st.sampled_from([130, 256]), seed=st.integers(0, 100))
def test_quant_matmul_op_padding(m, k, n, seed):
    """ops.py wrapper handles non-block-aligned shapes via padding."""
    key = jax.random.PRNGKey(seed)
    xq = _rand_int8(key, (m, k))
    wq = _rand_int8(jax.random.fold_in(key, 1), (k, n))
    sx = jnp.asarray(0.07, jnp.float32)
    sw = jax.random.uniform(jax.random.fold_in(key, 2), (n,), jnp.float32)
    out = ops.quant_matmul_op(xq, wq, sx, sw, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.quant_matmul_ref(xq, wq, sx, sw)),
                               rtol=1e-5)


# ---------------------------------------------------------- ternary_matmul
def test_ternary_matmul():
    key = jax.random.PRNGKey(0)
    m, k, n = 128, 512, 256
    xq = _rand_int8(key, (m, k))
    wt = _rand_int8(jax.random.fold_in(key, 1), (k, n), -1, 2)
    sx = jnp.asarray(0.02, jnp.float32)
    sw = jnp.full((n,), 0.5, jnp.float32)
    out = ternary_matmul(xq, wt, sx, sw, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.ternary_matmul_ref(xq, wt, sx, sw)),
        rtol=1e-6)
    assert set(np.unique(np.asarray(wt))) <= {-1, 0, 1}


# --------------------------------------------------------- split precision
@pytest.mark.parametrize("boundary_frac", [0.0, 0.25, 0.5, 1.0])
def test_split_precision_matmul(boundary_frac):
    key = jax.random.PRNGKey(3)
    m, k, n = 128, 512, 512
    bn = 128
    boundary = int(n * boundary_frac) // bn * bn
    x = jax.random.normal(key, (m, k), jnp.bfloat16)
    xq = _rand_int8(jax.random.fold_in(key, 1), (m, k))
    wb = jax.random.normal(jax.random.fold_in(key, 2), (k, n), jnp.bfloat16)
    wq = _rand_int8(jax.random.fold_in(key, 3), (k, n))
    sx = jnp.asarray(0.01, jnp.float32)
    sw = jax.random.uniform(jax.random.fold_in(key, 4), (n,), jnp.float32)
    out = split_precision_matmul(x, xq, sx, wb, wq, sw, boundary,
                                 interpret=True)
    expect = ref.split_precision_matmul_ref(x, xq, sx, wb, wq, sw, boundary)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-2, atol=2e-2)


def test_odimo_deployed_dense_matches_fake_quant():
    """Deployment path == search-time discretized fake-quant semantics."""
    from repro.core import quant
    key = jax.random.PRNGKey(7)
    m, k, n = 64, 256, 256
    x = jax.random.normal(key, (m, k))
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, n)) * 0.1
    assign = np.asarray(jax.random.bernoulli(
        jax.random.fold_in(key, 2), 0.5, (n,)).astype(np.int64))
    wls = quant.init_log_scale(w)
    xls = quant.init_log_scale(x)
    out = ops.odimo_deployed_dense(x, w, assign, wls, xls, interpret=True)
    # oracle: int8-domain columns use fake-quant x and w; bf16 columns plain
    xq = quant.fake_quant(x, xls, 8)
    wq8 = quant.fake_quant(w, wls, 8)
    lo = (xq @ wq8)
    hi = (x.astype(jnp.bfloat16) @ w.astype(jnp.bfloat16)).astype(jnp.float32)
    expect = jnp.where(jnp.asarray(assign)[None, :] == 0, lo, hi)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=0.05, atol=0.12)


# --------------------------------------------------------- flash attention
@pytest.mark.parametrize("B,H,KVH,Sq,Sk,D,causal", [
    (1, 4, 4, 256, 256, 64, True),
    (2, 8, 2, 256, 512, 64, True),     # GQA G=4
    (1, 4, 1, 512, 512, 128, True),    # MQA
    (1, 2, 2, 256, 256, 64, False),
])
def test_flash_attention(B, H, KVH, Sq, Sk, D, causal):
    key = jax.random.PRNGKey(B * H + Sq)
    q = jax.random.normal(key, (B, H, Sq, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, KVH, Sk, D), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, KVH, Sk, D), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, bq=128, bk=128,
                          interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_dtype_bf16():
    key = jax.random.PRNGKey(11)
    q = jax.random.normal(key, (1, 4, 256, 64), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 4, 256, 64), jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 4, 256, 64), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, bq=128, bk=128, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=3e-2, atol=3e-2)
