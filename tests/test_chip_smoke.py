"""Rehearse ``chip_smoke.py`` on the CPU: its phases run end to end at
smoke size (interpret-mode kernels), and its entry point refuses to run
without a TPU."""
import importlib.util
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_phases_at_smoke_size(tmp_path, capsys):
    smoke = _chip_smoke()
    summary = smoke.run_phases(
        "zamba2-1.2b", reduce=True, seed=0, out_dir=tmp_path, requests=3,
        prompt_len=12, gen_len=4, max_batch=2, layer_shape=(64, 256),
        layer_rows=8)
    out = capsys.readouterr().out
    assert summary["model"] == "zamba2-1.2b" and summary["d_model"] == 64
    assert summary["completed"] == summary["requests"] == 3
    assert summary["logits_max_abs_diff"] <= smoke.ATOL
    assert set(summary["layer_max_abs_diff"]) == {"split_precision",
                                                  "split_ternary"}
    assert set(summary["times"]) == {"init", "emit", "serve", "lower_bind",
                                     "compile", "layers"}
    # served through the plan: every planned layer bound, none declined
    assert " 0 unbound" in out and "declined at trace time" not in out
    assert (tmp_path / "mapping_tpu_v5e.json").is_file()


def test_chip_smoke_refuses_the_cpu(tmp_path, capsys):
    smoke = _chip_smoke()
    with pytest.raises(SystemExit) as exc:
        smoke.main(["--out", str(tmp_path)])
    assert exc.value.code != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert not any(tmp_path.iterdir())
    with pytest.raises(json.JSONDecodeError):
        json.loads(out.strip().splitlines()[-1] if out.strip() else "")
