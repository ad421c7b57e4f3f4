"""The precision control at a size a test can hold: the reference in the
next precision down reads above the limit, the program below it."""
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import control  # noqa: E402
import pytest  # noqa: E402
from test_bench_harness import CELL, MAMBA_CELL, make_root  # noqa: E402


@pytest.mark.parametrize("cell", [CELL, MAMBA_CELL])
def test_control_fails_and_program_passes(tmp_path, cell):
    root = make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    (r,) = control.readings(bench, cell, [5], 0.0, root=root)
    assert r["program"] <= r["limit"] < r["control"]
    assert r["control"] >= 3 * r["program"]
