"""Trace reduction, kernel cost functions and request arithmetic of the
benchmark, on small made-up inputs whose answers are known."""
import json
import math
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import devtrace  # noqa: E402
import roofline  # noqa: E402
import stats  # noqa: E402
from devtrace import Event  # noqa: E402

KERNEL = ("%quant_matmul_op.7 = f32[8,128]{1,0:T(8,128)} "
          "custom-call(s8[8,512]{1,0} %fusion.1, s8[512,128]{1,0} %p.2)")


def _events():
    # ns: ops at [0,10) [5,20) [30,40) [60,100); the window is [0, 100);
    # fusion.3 reads the kernel's output and is no kernel event
    return [Event("%fusion.1 = bf16[8,512]{1,0} fusion(bf16[8,512] %p.0)",
                  0, 10),
            Event(KERNEL, 5, 15), Event(KERNEL, 30, 10),
            Event("%fusion.3 = bf16[8,128]{1,0} fusion(f32[8,128]{1,0} "
                  "%quant_matmul_op.7)", 60, 40)]


def test_busy_union_idle_and_gaps():
    ev = _events()
    assert devtrace.busy_intervals(ev) == [(0, 20), (30, 40), (60, 100)]
    assert devtrace.busy_ns(ev) == 70
    assert devtrace.idle_gaps(ev, 0, 100) == [(20, 30), (40, 60)]
    host = [Event("bench.window", 0, 100), Event("bench.round 0", 1, 98),
            Event("PjitFunction(chunk_fn)", 45, 10)]
    gaps = devtrace.top_gaps(ev, host, 0, 100)
    assert [g[0] for g in gaps] == ["PjitFunction(chunk_fn)", "bench.round 0"]
    assert [g[1] for g in gaps] == pytest.approx([20e-9, 10e-9])
    clipped = devtrace.clip(ev, 8, 35)
    assert [(e.start_ns, e.dur_ns) for e in clipped] == [(8, 2), (8, 12),
                                                         (30, 5)]


def test_kernel_time_and_top_ops():
    ev = _events()
    assert devtrace.kernel_ns(ev, "quant_matmul") == (25, 2)
    assert devtrace.kernel_ns(ev, "split_ternary") == (0, 0)
    top = devtrace.top_ops(ev, 2)
    assert [t[0] for t in top] == ["fusion.3 bf16[8,128]",
                                   "quant_matmul_op.7 f32[8,128]"]
    assert [t[1] for t in top] == pytest.approx([40e-9, 25e-9])


def test_quant_matmul_cost_at_a_known_shape():
    ops, nbytes = roofline.quant_matmul_cost(4, 2048, 8192)
    assert ops == 2 * 4 * 2048 * 8192
    assert nbytes == 2048 * 8192 + 4 * 2048 + 4 * 4 * 8192 + 4 * 8192
    pk = roofline.peaks_for("TPU v5 lite")
    # four rows: bound by HBM, not by the MXU
    assert roofline.least_seconds(ops, nbytes, pk) == pytest.approx(
        nbytes / 819e9)
    big = roofline.quant_matmul_cost(8192, 2048, 8192)
    assert roofline.least_seconds(*big, pk) == pytest.approx(big[0] / 393e12)


def test_unknown_device_is_an_error():
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks_for("TPU v9 imaginary")


def test_percentile():
    xs = [5.0, 1.0, 4.0, 2.0, 3.0]
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 90) == pytest.approx(4.6)
    assert stats.percentile(xs, 90) == pytest.approx(
        float(np.percentile(xs, 90)))


def test_serving_metrics_cover_all_requests():
    done = [SimpleNamespace(tokens=[1] * n, ttft_s=t, finish_s=f)
            for n, t, f in [(10, 0.1, 1.0), (1, 0.2, 0.2), (4, 0.5, 0.8)]]
    m = stats.serving_metrics(done, window_s=2.0)
    assert m["output_tok_s"] == 15 / 2.0
    assert m["ttft_p90_ms"] == pytest.approx(1e3 * (0.2 + 0.8 * 0.3))
    # the one-token request has no gap after its first token
    assert m["tpot_p90_ms"] == pytest.approx(
        1e3 * stats.percentile([0.1, 0.1], 90))
    assert math.isfinite(m["tpot_p90_ms"])


def test_recorded_v5e_decode_trace():
    """300 consecutive device ops of a zamba2 decode step, recorded from the
    XLA Ops line of a TPU v5 lite trace (names cut to 160 characters)."""
    rec = json.loads((Path(__file__).resolve().parent / "data" /
                      "v5e_decode_events.json").read_text())
    ev = [Event(e["name"], e["start_ns"], e["dur_ns"]) for e in rec]
    t0, t1 = ev[0].start_ns, max(e.end_ns for e in ev)
    busy = devtrace.busy_ns(ev)
    assert 0 < busy <= t1 - t0
    ns, count = devtrace.kernel_ns(ev, "quant_matmul")
    assert count == 10 and 0 < ns < busy
    names = [n for n, _ in devtrace.top_ops(ev)]
    assert not any(n.startswith("while") for n in names)
    assert all(" = " not in n for n in names)
