"""Percentile and rate arithmetic over all requests of a window."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(xs: Sequence[float], p: float) -> float:
    """Linear interpolation between order statistics (p in [0, 100])."""
    xs = sorted(float(x) for x in xs)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * p / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def serving_metrics(done, window_s: float) -> dict:
    """The end-to-end serving numbers over every completed request:
    generated tokens over the window's wall time, and the 90th percentiles
    of time to first token and of the time per output token after the
    first."""
    tokens = sum(len(r.tokens) for r in done)
    tpot = [(r.finish_s - r.ttft_s) / (len(r.tokens) - 1)
            for r in done if len(r.tokens) > 1]
    return {"output_tok_s": tokens / window_s,
            "ttft_p90_ms": 1e3 * percentile([r.ttft_s for r in done], 90),
            "tpot_p90_ms": 1e3 * percentile(tpot, 90)}
