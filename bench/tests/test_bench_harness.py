"""The harness end to end on the CPU at a small size, its refusal of the
CPU, its set-up check of the program's weights against the reference's
widths, its correctness check against planted faults, and BENCHMARK.json
against the benchmark contract."""
import json
import re
import shutil
import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

CELL = "zamba2-smoke.smoke"
YI_CELL = "yi-smoke.smoke"
MAMBA_CELL = "mamba2-smoke.smoke"
SMOKE = (("zamba2-smoke", CELL), ("yi-smoke", YI_CELL),
         ("mamba2-smoke", MAMBA_CELL))
DUMMY_METRIC = '''"""A metric added as a file of its own: rounds' requests per round."""


def read(ctx):
    return float(len(ctx["done"]))
'''


def make_root(tmp_path: Path) -> Path:
    """A checkout holding the benchmark plus one configuration, one mix and
    one per-layer metric added as new files and entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name, _ in SMOKE:
        shutil.copy(DATA / f"{name}.json",
                    root / "bench" / "configs" / f"{name}.json")
    shutil.copy(DATA / "smoke.json", root / "bench" / "traffic" / "smoke.json")
    (root / "bench" / "metrics" / "requests_done.py").write_text(DUMMY_METRIC)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name, cell in SMOKE:
        bench["configs"].append({
            "name": name, "source": "https://arxiv.org/abs/2411.15242",
            "file": f"bench/configs/{name}.json",
            "reduced": ["n_layers", "d_model"], "why": "CPU test size"})
        bench["workloads"].append({"name": cell, "config": name,
                                   "traffic": "smoke", "chips": 1,
                                   "why": "CPU test size"})
    for m in bench["per_layer"]:
        m["workloads"] = m["workloads"] + [c for _, c in SMOKE]
    bench["per_layer"].append({
        "name": "requests_done", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "engine host loop",
        "moves": "output_tok_s", "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run(root, seed=3, trace=False, cell=CELL, **kw):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    return harness.run_cell(bench, cell, seed=seed, seconds=0.0,
                            trace=trace, log=lambda s: None, t_start=0.0,
                            root=root, **kw)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


def test_smoke_run_is_correct_and_reports_every_metric(root):
    out = run(root, trace=True)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] == 6
    assert list(out)[-1] == "checks"
    got = set(out["metrics"])
    # host-side readers read on the CPU; chip-only ones stay silent
    assert {"engine_host_share", "decode_step_ms", "prefill_chunk_ms",
            "chunk_row_use", "requests_done"} <= got
    assert not got & {"quant_matmul_roofline", "step_mfu"}
    assert out["metrics"]["requests_done"]["value"] == 6.0
    assert 0 < out["metrics"]["chunk_row_use"]["value"] <= 100
    gap = out["checks"]["max_logit_gap"]
    assert 0 <= gap["value"] < gap["limit"]


@pytest.mark.parametrize("cell", [CELL, YI_CELL, MAMBA_CELL])
def test_end_to_end_metrics_without_trace(root, cell):
    out = run(root, seed=2 ** 31 + 11, cell=cell)
    assert out["correct"] is True
    assert {"output_tok_s", "ttft_p90_ms", "tpot_p90_ms",
            "setup_s"} <= set(out["metrics"])
    assert "breakdown" not in out and "busy_s" not in out["device"]


def _patch(monkeypatch, name, wrap):
    from repro.serving import Engine
    monkeypatch.setattr(Engine, name, wrap(getattr(Engine, name)))


@pytest.mark.parametrize("cell", [CELL, MAMBA_CELL])
def test_altered_token_fails_the_check(root, monkeypatch, cell):
    def wrap(orig):
        def postdecode(self, batch, tok, *a, **k):
            tok = (np.asarray(tok) + 1) % self.cfg.vocab
            return orig(self, batch, tok, *a, **k)
        return postdecode
    _patch(monkeypatch, "_postdecode", wrap)
    out = run(root, cell=cell)
    assert out["correct"] is False
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("cell", [CELL, MAMBA_CELL])
def test_state_left_unchanged_fails_the_check(root, monkeypatch, cell):
    def wrap(orig):
        def decode_groups(self, batch, *a, **k):
            caches = batch.caches
            orig(self, batch, *a, **k)
            batch.caches = caches          # the step's cache writes vanish
        return decode_groups
    _patch(monkeypatch, "_decode_groups", wrap)
    out = run(root, cell=cell)
    assert out["correct"] is False


def test_main_refuses_the_cpu(capsys):
    assert harness.main(["--workload", "mamba2-1.3b.chat", "--seed", "1",
                         "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


def program_tree(name, **program_sizes):
    """The configuration's sizes and the program's tree of weight shapes,
    built with ``program_sizes`` in place of the file's."""
    import jax
    from repro.models import transformer as T
    path = DATA / f"{name}.json"
    config = json.loads((path if path.exists() else
                         BENCH / "configs" / f"{name}.json").read_text())
    sizes = config["sizes"]
    config["sizes"] = {**sizes, **program_sizes}
    cfg = harness.program_cfg(config)
    return sizes, jax.eval_shape(
        lambda: T.init_lm(jax.random.PRNGKey(0), cfg))


def reference(path=BENCH / "reference" / "lm.py"):
    return harness.check_mod.load_reference(path)


@pytest.mark.parametrize("name", ["zamba2-smoke", "yi-smoke", "mamba2-smoke",
                                  "mamba2-1.3b"])
def test_reference_declares_the_program_tree(name):
    sizes, tree = program_tree(name)
    ref = reference()
    want = harness._leaf_paths(ref.param_shapes(sizes), harness._is_shape)
    assert {p: tuple(s.shape) for p, s in
            harness._leaf_paths(tree).items()} == want
    harness.check_shapes(tree, sizes, ref)


def _set(tree, path, shape):
    """Give the leaf at ``path`` the shape ``shape``; None removes it."""
    import jax
    *up, leaf = path.split("/")
    for k in up:
        tree = tree[int(k) if k.isdigit() else k]
    if shape is None:
        del tree[leaf]
    else:
        tree[leaf] = jax.ShapeDtypeStruct(shape, "float32")


@pytest.mark.parametrize("name,program_sizes,edit,leaf", [
    ("mamba2-smoke", {}, ("units/0/mamba/in_proj/w", (2, 64, 289)),
     "units/0/mamba/in_proj/w"),
    ("mamba2-smoke", {}, ("units/0/mamba/conv_w", (2, 4, 168)),
     "units/0/mamba/conv_w"),
    ("mamba2-smoke", {"vocab": 120}, None, "emb"),
    ("yi-smoke", {"head_dim": 8}, None, "units/0/attn/wq/w"),
    ("zamba2-smoke", {"d_ff": 96}, None, "shared/ffn/up/w"),
    ("zamba2-smoke", {}, ("shared/attn/wq/b", (64,)), "shared/attn/wq/b"),
    ("yi-smoke", {}, ("units/0/norm2/scale", None), "units/0/norm2/scale"),
], ids=["in_proj_short", "conv_w_width", "emb_vocab", "wq_head_dim",
        "ffn_width", "extra_leaf", "missing_leaf"])
def test_width_departure_is_refused(name, program_sizes, edit, leaf):
    sizes, tree = program_tree(name, **program_sizes)
    if edit is not None:
        _set(tree, *edit)
    with pytest.raises(harness.BenchError, match=f"{leaf} wants"):
        harness.check_shapes(tree, sizes, reference())


def test_grouped_mamba2_reference_is_accepted():
    sizes, tree = program_tree("mamba2-smoke")
    # d 64, di 128, N 16, H 2, two groups: in_proj 2di + 2GN + H, conv di + 2GN
    for path, shape in (("units/0/mamba/in_proj/w", (2, 64, 322)),
                        ("units/0/mamba/conv_w", (2, 4, 192)),
                        ("units/0/mamba/conv_b", (2, 192))):
        _set(tree, path, shape)
    harness.check_shapes(tree, {**sizes, "mamba_ngroups": 2},
                         reference(DATA / "grouped_mamba2.py"))
    with pytest.raises(harness.BenchError, match="in_proj/w wants"):
        harness.check_shapes(tree, sizes, reference())


def test_lm_reference_refuses_mamba2_groups():
    sizes, _ = program_tree("mamba2-smoke")
    with pytest.raises(ValueError, match="mamba_ngroups 2"):
        reference().param_shapes({**sizes, "mamba_ngroups": 2})


def test_reference_without_param_shapes_is_refused(root, monkeypatch):
    bare = types.ModuleType("bare_reference")
    monkeypatch.setattr(harness.check_mod, "load_reference",
                        lambda path: bare)

    def make_params(*a, **k):
        raise AssertionError("weights made before the width check")
    monkeypatch.setattr(harness, "make_params", make_params)
    with pytest.raises(harness.BenchError, match="param_shapes"):
        run(root, cell=MAMBA_CELL)


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_keeps_the_contract():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    for p in b["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/")
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"] and cfg["name"] == c["name"]
        assert (ROOT / cfg["reference"]).is_file()
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    names = [c["name"] for c in b["configs"]] + \
        [w["name"] for w in b["workloads"]] + \
        [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert all(NAME.match(n) for n in names)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert len(w["why"]) <= 200
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert m["moves"] in e2e and UNIT.match(m["unit"])
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    cells = {w["name"] for w in b["workloads"]}
    assert all(set(m.get("workloads", cells)) <= cells
               for m in b["end_to_end"] + b["per_layer"])
    assert len(json.dumps(b)) < 64 * 1024
