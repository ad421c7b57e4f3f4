"""Launch/roofline machinery tests (no 512-device requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.launch import specs as SP
from repro.roofline import analysis as RA


@pytest.fixture(scope="module", autouse=True)
def _load():
    base.load_all()


def test_shapes_cover_40_cells():
    assert len(SP.SHAPES) == 4
    assert len(base.names()) == 11


def test_long500k_gating():
    assert SP.cell_is_runnable(base.get("xlstm-1.3b"), "long_500k")
    assert SP.cell_is_runnable(base.get("zamba2-1.2b"), "long_500k")
    assert SP.cell_is_runnable(base.get("h2o-danube-3-4b"), "long_500k")
    assert not SP.cell_is_runnable(base.get("yi-9b"), "long_500k")
    assert not SP.cell_is_runnable(base.get("arctic-480b"), "long_500k")


@pytest.mark.parametrize("arch", ["yi-9b", "arctic-480b", "seamless-m4t-large-v2"])
@pytest.mark.parametrize("shape", ["train_4k", "prefill_32k", "decode_32k"])
def test_input_specs_wellformed(arch, shape):
    cfg = base.get(arch)
    specs = SP.input_specs(cfg, shape)
    for leaf in jax.tree.leaves(specs):
        assert isinstance(leaf, jax.ShapeDtypeStruct)
    meta = SP.SHAPES[shape]
    if meta["kind"] == "train":
        assert specs["batch"]["tokens"].shape == (meta["batch"], meta["seq"])
    elif meta["kind"] == "decode":
        assert specs["token"].shape == (meta["batch"],)
        assert "frontend" not in specs  # cross-KV lives in the cache


def test_param_counts_match_public_sizes():
    """Sanity: parameter totals are in the right ballpark for the names."""
    expected = {
        "yi-9b": (8e9, 10e9),
        "command-r-35b": (28e9, 40e9),  # tied emb counted once
        "nemotron-4-15b": (14e9, 17e9),
        "arctic-480b": (430e9, 520e9),
        "deepseek-v2-lite-16b": (14e9, 18e9),
        "xlstm-1.3b": (1.0e9, 2.2e9),
        "zamba2-1.2b": (0.9e9, 1.6e9),
        "h2o-danube-3-4b": (3.4e9, 4.6e9),
        "llama-3.2-vision-11b": (8e9, 12e9),
        "seamless-m4t-large-v2": (1.4e9, 2.8e9),
    }
    for name, (lo, hi) in expected.items():
        n = RA.param_counts(base.get(name))["total"]
        assert lo <= n <= hi, (name, n)


def test_moe_active_params_below_total():
    c = RA.param_counts(base.get("arctic-480b"))
    assert c["active"] < 0.1 * c["total"]  # top-2 of 128 experts
    c = RA.param_counts(base.get("deepseek-v2-lite-16b"))
    assert c["active"] < 0.5 * c["total"]


def test_parse_collectives_scoped():
    from repro.launch.dryrun import parse_collectives
    hlo = """
ENTRY %main (p0: f32[8,8]) -> f32[8,8] {
  %ag = f32[64,8]{1,0} all-gather(%p0), replica_groups={}
  ROOT %r = f32[8,8] add(%p0, %p0)
}
%while_body_1 (p: f32[4]) -> f32[4] {
  %ar = bf16[2048,512]{1,0} all-reduce(%x), to_apply=%sum
}
"""
    out = parse_collectives(hlo)
    assert out["bytes"]["all-gather"]["top"] == 64 * 8 * 4
    assert out["bytes"]["all-reduce"]["loop"] == 2048 * 512 * 2


def test_analytic_flops_positive_all_cells():
    for arch in base.names():
        cfg = base.get(arch)
        for shape in SP.SHAPES:
            if not SP.cell_is_runnable(cfg, shape):
                continue
            fl = RA.hlo_flops(cfg, shape)
            assert fl["total"] > 0 and fl["model"] > 0, (arch, shape)
            by = RA.hlo_bytes(cfg, shape)
            assert by > 0


def test_int8_variants_reduce_bytes():
    import dataclasses
    cfg = base.get("yi-9b")
    b0 = RA.hlo_bytes(cfg, "decode_32k")
    b1 = RA.hlo_bytes(dataclasses.replace(cfg, kv_cache_dtype="int8"),
                      "decode_32k")
    b2 = RA.hlo_bytes(dataclasses.replace(cfg, kv_cache_dtype="int8",
                                          serve_weight_dtype="int8"),
                      "decode_32k")
    assert b1 < 0.65 * b0       # cache dominates
    assert b2 < b1
