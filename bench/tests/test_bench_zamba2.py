"""The Zamba2 reference against transformers' `Zamba2ForCausalLM` on the
CPU, at a size with two Mamba2 groups and two shared blocks used twice
each, on the same random weights, float32 logits; and whole runs of a
test-size `zamba2-7b` cell (`data/zamba2-7b-smoke.json`) through the
harness: correct, its decode byte counts read, planted faults caught, the
int4 control above the limit and the program below it; and the decode
roofline reader's engine calls on made-up and recorded device events."""
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import check  # noqa: E402
import control  # noqa: E402
import devtrace  # noqa: E402
import harness  # noqa: E402
import test_bench_harness as th  # noqa: E402
from devtrace import Event  # noqa: E402

roofline_reader = harness.metric_reader("decode_hbm_roofline", ROOT)

CELL = "zamba2-7b-smoke.smoke"

SIZES = {"d_model": 64, "vocab": 50, "n_layers": 7,
         "pattern": ["mamba", "hybrid", "mamba", "hybrid", "hybrid", "mamba",
                     "hybrid"],
         "n_heads": 4, "n_kv_heads": 4, "head_dim": 32, "d_ff": 96,
         "rope_theta": 10000.0, "ssm_state": 16, "mamba_expand": 2,
         "mamba_headdim": 16, "mamba_d_conv": 4, "mamba_ngroups": 2,
         "mem_blocks": 2, "adapter_rank": 8, "tie_embeddings": True}


def reference():
    return check.load_reference(BENCH / "reference" / "zamba2.py")


def random_params(shapes, seed):
    """Every leaf random, norm gains and D away from one, so that a
    weight read in the wrong place shows."""
    import jax
    rng = np.random.default_rng(seed)

    def leaf(path, shape):
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        if name.endswith("scale") or name.endswith("/D"):
            v = 1.0 + 0.2 * rng.standard_normal(shape)
        elif name.endswith("A_log"):
            v = np.log(rng.uniform(1.0, 16.0, shape))
        elif name.endswith("dt_bias"):
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shape))
            v = dt + np.log(-np.expm1(-dt))
        elif name.endswith("conv_w") or name.endswith("conv_b"):
            v = 0.5 * rng.standard_normal(shape)
        elif name == "emb":
            v = rng.standard_normal(shape)
        else:
            v = rng.standard_normal(shape) / np.sqrt(shape[-2])
        return v.astype(np.float32)

    return jax.tree_util.tree_map_with_path(
        leaf, shapes, is_leaf=lambda x: isinstance(x, tuple) and
        all(isinstance(i, int) for i in x))


def hf_model(params, sizes):
    """`Zamba2ForCausalLM` holding ``params`` (the program's tree)."""
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip("transformers")
    from transformers.models.zamba2 import modeling_zamba2 as mz
    d = sizes["d_model"]
    cfg = tf.Zamba2Config(
        vocab_size=sizes["vocab"], hidden_size=d,
        num_hidden_layers=sizes["n_layers"],
        layers_block_type=list(sizes["pattern"]),
        mamba_d_state=sizes["ssm_state"], mamba_d_conv=sizes["mamba_d_conv"],
        mamba_expand=sizes["mamba_expand"],
        mamba_ngroups=sizes["mamba_ngroups"],
        n_mamba_heads=sizes["mamba_expand"] * d // sizes["mamba_headdim"],
        intermediate_size=sizes["d_ff"], hidden_act="gelu",
        num_attention_heads=sizes["n_heads"],
        num_key_value_heads=sizes["n_kv_heads"],
        num_mem_blocks=sizes["mem_blocks"], adapter_rank=sizes["adapter_rank"],
        use_mem_rope=True, rope_theta=sizes["rope_theta"],
        use_shared_attention_adapter=False, rms_norm_eps=1e-5,
        attn_implementation="eager")
    assert cfg.attention_head_dim == sizes["head_dim"]
    with _quiet():
        model = mz.Zamba2ForCausalLM(cfg).eval()
    t = lambda a: torch.tensor(np.asarray(a, np.float32))
    lin = lambda mod, w: setattr(mod, "weight", torch.nn.Parameter(t(w).T))
    vec = lambda mod, name, a: setattr(mod, name, torch.nn.Parameter(t(a)))
    m = model.model
    m.embed_tokens.weight.data[:] = t(params["emb"])
    vec(m.final_layernorm, "weight", params["final_norm"]["scale"])
    uses = 0
    for i, kind in enumerate(sizes["pattern"]):
        lp = {k: _first(v) for k, v in params["units"][i].items()}
        layer = m.layers[i]
        dec = layer if kind == "mamba" else layer.mamba_decoder
        vec(dec.input_layernorm, "weight", lp["norm1"]["scale"])
        mx, pm = dec.mamba, lp["mamba"]
        lin(mx.in_proj, pm["in_proj"]["w"])
        lin(mx.out_proj, pm["out_proj"]["w"])
        mx.conv1d.weight = torch.nn.Parameter(t(pm["conv_w"]).T[:, None, :])
        vec(mx.conv1d, "bias", pm["conv_b"])
        for k in ("dt_bias", "A_log", "D"):
            vec(mx, k, pm[k])
        # transformers' torch path clamps dt below at time_step_min; the
        # published kernels' path (time_step_limit None) does not
        mx.time_step_min = 0.0
        vec(mx.norm, "weight", pm["norm"]["scale"])
        if kind == "hybrid":
            lin(layer.linear, lp["linear"]["w"])
            st = layer.shared_transformer
            sb = params["shared"][uses % sizes["mem_blocks"]]
            adapter = st.feed_forward.gate_up_proj_adapter_list[uses]
            lin(adapter[0], lp["lora_a"]["w"])
            lin(adapter[1], lp["lora_b"]["w"])
            vec(st.input_layernorm, "weight", sb["norm1"]["scale"])
            vec(st.pre_ff_layernorm, "weight", sb["norm2"]["scale"])
            for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                                 ("wv", "v_proj"), ("wo", "o_proj")):
                lin(getattr(st.self_attn, theirs), sb["attn"][ours]["w"])
            lin(st.feed_forward.gate_up_proj, sb["ffn"]["gate_up"]["w"])
            lin(st.feed_forward.down_proj, sb["ffn"]["down"]["w"])
            uses += 1
    assert model.lm_head.weight.data_ptr() == m.embed_tokens.weight.data_ptr()
    return model


def _first(v):
    import jax
    return jax.tree.map(lambda a: a[0], v)


class _quiet:
    """Silence transformers' slow-path notice while building the model."""

    def __enter__(self):
        import logging
        self.log = logging.getLogger("transformers")
        self.level = self.log.level
        self.log.setLevel(logging.ERROR)

    def __exit__(self, *exc):
        self.log.setLevel(self.level)


@pytest.mark.parametrize("seed", [0, 1])
def test_reference_matches_transformers_zamba2(seed):
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    import jax
    with jax.default_matmul_precision("highest"):
        ref = reference()
        params = random_params(ref.param_shapes(SIZES), seed)
        tokens = np.random.default_rng(seed + 7).integers(
            0, SIZES["vocab"], 40)
        ours = np.asarray(ref.logits(params, SIZES, tokens,
                                     np.arange(len(tokens))))
    with torch.no_grad():
        theirs = hf_model(params, SIZES)(
            torch.tensor(tokens[None])).logits[0].numpy()
    # float32 on both sides, summed in different orders: agreement to a
    # few float32 ulps of the logits' size
    scale = np.abs(theirs).max()
    np.testing.assert_allclose(ours, theirs, rtol=0, atol=2e-5 * scale)


def test_reference_counts_each_block_by_its_uses():
    ref = reference()
    assert ref.layer_uses(SIZES, "shared/0/attn/wq") == 2
    assert ref.layer_uses(SIZES, "shared/1/ffn/down") == 2
    assert ref.layer_uses({**SIZES, "n_layers": 4}, "shared/1/attn/wq") == 1
    assert ref.layer_uses(SIZES, "units/1/linear") == 1


# --------------------------------------------------------------------------
# whole runs of a test-size cell
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The test checkout of `test_bench_harness`, plus the test-size
    zamba2-7b configuration and its cell, added as a file and entries."""
    root = th.make_root(tmp_path_factory.mktemp("bench_zamba2"))
    name = "zamba2-7b-smoke"
    shutil.copy(DATA / f"{name}.json", root / "bench" / "configs" /
                f"{name}.json")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": name, "source": "https://arxiv.org/abs/2411.15242",
        "file": f"bench/configs/{name}.json",
        "reduced": ["num_hidden_layers", "hidden_size"],
        "why": "CPU test size"})
    bench["workloads"].append({"name": CELL, "config": name,
                               "traffic": "smoke", "chips": 1,
                               "why": "CPU test size"})
    for m in bench["per_layer"]:
        m["workloads"] = m["workloads"] + [CELL]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_smoke_run_is_correct_and_reads_the_decode_bytes(root):
    keep = {}
    out = th.run(root, trace=True, cell=CELL, keep=keep)
    assert out["correct"] is True and out["failed"] == 0
    assert "decode_hbm_roofline" not in out["metrics"]   # no chip, no peaks
    gap = out["checks"]["max_logit_gap"]
    assert 0 <= gap["value"] < gap["limit"]
    s, ref, sizes = keep["stats"], keep["reference"], keep["sizes"]
    # each decode call's slots read their whole cache in both hybrid uses
    assert s["decode_kv_tokens"] >= 2 * s["decode_rows"] > 0
    ctx = {"stats": s, "reference": ref, "sizes": sizes, "layers": []}
    head = 2.0 * sizes["d_model"] * sizes["vocab"]
    assert roofline_reader.least_bytes(ctx) == pytest.approx(
        s["decode_kv_tokens"] * ref.kv_bytes_per_token(sizes)
        + head * s["decode_steps"]
        + 2 * s["decode_rows"] * ref.state_bytes_per_slot(sizes))


def _events(*calls):
    """Device events of engine calls 1 ms apart, each ``(quant_matmul
    rows or None, ops)`` run back to back, ops of 10 us."""
    out, t = [], 0.0
    for rows, ops in calls:
        for i in range(ops):
            name = "%fusion.1 = bf16[8,128]{1,0} fusion(...)"
            if rows is not None and i == 0:
                name = (f"%quant_matmul_op.3 = f32[{rows},2048]{{1,0}} "
                        f"custom-call(s8[{rows},2048]{{1,0}} %a)")
            out.append(Event(name, t, 10_000.0))
            t += 10_000.0
        t += 1e6
    return out


def _roofline_ctx(events, decode_steps):
    stats = {"decode_steps": decode_steps, "decode_kv_tokens": 1000,
             "decode_rows": 20}
    return {"stats": stats, "reference": reference(), "sizes": SIZES,
            "layers": [("l", "quant_matmul", 64, 128, 1.0)],
            "mix": {"engine": {"max_batch": 16}},
            "peaks": {"hbm_byte_s": 1e9}, "trace": {"events": events}}


def test_decode_roofline_divides_by_the_decode_calls_device_time():
    ev = _events((4096, 5), (None, 2), (16, 3), (16, 4), (16, 2))
    kinds = [k for k, _ in roofline_reader.calls(ev, 16)]
    assert kinds == ["chunk", "other", "decode", "decode", "decode"]
    ctx = _roofline_ctx(ev, decode_steps=3)
    least = roofline_reader.least_bytes(ctx)
    want = 100.0 * least / 1e9 / (9 * 10_000.0 * 1e-9)
    assert roofline_reader.read(ctx) == pytest.approx(want)


@pytest.mark.parametrize("steps", [2, 4])
def test_decode_roofline_is_silent_where_calls_and_steps_differ(steps):
    ev = _events((4096, 5), (16, 3), (16, 4), (16, 2))
    assert roofline_reader.read(_roofline_ctx(ev, steps)) is None


def test_decode_roofline_finds_one_call_in_a_recorded_v5e_decode_step():
    """The recorded zamba2 decode step (batch 32, bench_reduce's data) is
    one call, of kernels on 32 rows: a decode call at batch 32, a chunk
    call's rows at batch 16."""
    rec = json.loads((DATA / "v5e_decode_events.json").read_text())
    ev = [Event(e["name"], e["start_ns"], e["dur_ns"]) for e in rec]
    assert {roofline_reader.kernel_rows(e.name) for e in ev} == {None, 32}
    (got,) = roofline_reader.calls(ev, 32)
    assert got == ("decode", devtrace.busy_ns(ev))
    assert roofline_reader.calls(ev, 16)[0][0] == "chunk"


def test_end_to_end_metrics_without_trace(root):
    out = th.run(root, seed=2 ** 31 + 11, cell=CELL)
    assert out["correct"] is True
    assert {"output_tok_s", "ttft_p90_ms", "tpot_p90_ms",
            "setup_s"} <= set(out["metrics"])


def test_altered_token_fails_the_check(root, monkeypatch):
    def wrap(orig):
        def postdecode(self, batch, tok, *a, **k):
            tok = (np.asarray(tok) + 1) % self.cfg.vocab
            return orig(self, batch, tok, *a, **k)
        return postdecode
    th._patch(monkeypatch, "_postdecode", wrap)
    out = th.run(root, cell=CELL)
    assert out["correct"] is False


def test_state_left_unchanged_fails_the_check(root, monkeypatch):
    def wrap(orig):
        def decode_groups(self, batch, *a, **k):
            caches = batch.caches
            orig(self, batch, *a, **k)
            batch.caches = caches          # the step's cache writes vanish
        return decode_groups
    th._patch(monkeypatch, "_decode_groups", wrap)
    out = th.run(root, cell=CELL)
    assert out["correct"] is False


def test_control_fails_and_program_passes(root):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    (r,) = control.readings(bench, CELL, [5], 0.0, root=root)
    assert r["program"] <= r["limit"] < r["control"]


@pytest.mark.parametrize("name", ["zamba2-7b-smoke", "zamba2-7b"])
def test_reference_declares_the_program_tree(name):
    sizes, tree = th.program_tree(name)
    path = DATA / f"{name}.json"
    config = json.loads((path if path.exists() else
                         BENCH / "configs" / f"{name}.json").read_text())
    ref = check.load_reference(ROOT / config["reference"])
    want = harness._leaf_paths(ref.param_shapes(sizes), harness._is_shape)
    assert {p: tuple(s.shape) for p, s in
            harness._leaf_paths(tree).items()} == want
    harness.check_shapes(tree, sizes, ref)
