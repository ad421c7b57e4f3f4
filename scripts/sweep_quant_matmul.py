#!/usr/bin/env python3
"""Time `quant_matmul_op` under candidate block shapes on one TPU.

    PYTHONPATH=src python scripts/sweep_quant_matmul.py [--out FILE]

For each (M, K, N) of the mamba2-1.3b chat cell's planned projections
(chunk step M=4096, decode step M=16; ``in_proj`` K=2048 N=8512,
``out_proj`` K=4096 N=2048) it runs each candidate's op 50 times (chunk)
or 1000 times (decode) inside one jitted loop, each call's scale chained
to the last call's output so that no call can be hoisted, and reports the best of three
loops in microseconds per call with the call's share of the int8 roofline
(`bench/peaks.json`'s v5e peaks, operations and bytes of one call read
once).  Every candidate's output is compared bit for bit with
`ref.quant_matmul_ref` on the chip.  The candidate ``chosen`` is
`ops.quant_matmul_blocks`.  Without a TPU it exits 2.

The loop reads the same weight on every call, and XLA keeps that
loop-invariant operand in VMEM: a decode row reads above 100% of the HBM
roofline because its weight never streams from HBM.  The served decode
step reads its kernels' weights from VMEM too: XLA slices each layer's
weight out of the scan's stack into VMEM (an ``s8[K,N]...S(1)`` fusion
before the custom call), and that copy, not the kernel, streams it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

SHAPES = [(4096, 2048, 8512), (4096, 4096, 2048),
          (16, 2048, 8512), (16, 4096, 2048)]
PREFILL = [(128, 128, 512), (256, 1024, 0), (512, 1024, 0), (256, 2048, 0),
           (512, 512, 0), (1024, 512, 0), (1024, 1024, 0), (512, 2048, 0),
           (2048, 512, 0), (1024, 2048, 0), (2048, 1024, 0)]
DECODE = [(128, 128, 512), (0, 256, 0), (0, 512, 0), (0, 1024, 0),
          (0, 2048, 0), (0, 4096, 0)]
INT8_OP_S, HBM_BYTE_S = 393e12, 819e9


def candidates(m: int, k: int, n: int):
    """[(label, (bm, bn, bk) or None for the chooser)]; 0 = the whole
    axis (M rows, K)."""
    rows = PREFILL if m > 256 else DECODE
    out = [("chosen", None)]
    for bm, bn, bk in rows:
        b = (bm or m, bn, bk or k)
        out.append(("x".join(map(str, b)), b))
    return out


def roofline_s(m: int, k: int, n: int) -> float:
    ops, nbytes = 2 * m * k * n, k * n + m * k + 4 * m * n + 4 * n
    return max(ops / INT8_OP_S, nbytes / HBM_BYTE_S)


def time_op(fn, x, w, sx, sw, reps: int) -> float:
    """Seconds per call: best of three jitted loops of ``reps`` calls."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def loop(x, w, sx, sw, n):
        def body(_, s):
            return s + 0.0 * fn(x, w, s, sw)[0, 0]
        return jax.lax.fori_loop(0, n, body, sx)

    n = jnp.asarray(reps, jnp.int32)
    jax.block_until_ready(loop(x, w, sx, sw, n))
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        jax.block_until_ready(loop(x, w, sx, sw, n))
        best = min(best, time.perf_counter() - t)
    return best / reps


def sweep(log=print):
    import jax
    import jax.numpy as jnp
    from functools import partial
    from repro.kernels import ops, ref

    rows = []
    key = jax.random.PRNGKey(0)
    for m, k, n in SHAPES:
        x = jax.random.randint(key, (m, k), -127, 128, dtype=jnp.int8)
        w = jax.random.randint(jax.random.fold_in(key, 1), (k, n), -127, 128,
                               dtype=jnp.int8)
        sx = jnp.asarray(0.013, jnp.float32)
        sw = jax.random.uniform(jax.random.fold_in(key, 2), (n,), jnp.float32)
        want = np.asarray(jax.jit(ref.quant_matmul_ref)(x, w, sx, sw))
        floor = roofline_s(m, k, n)
        reps = 50 if m > 256 else 1000
        for label, b in candidates(m, k, n):
            kw = {} if b is None else dict(zip(("bm", "bn", "bk"), b))
            fn = partial(ops.quant_matmul_op, **kw)
            got = np.asarray(jax.jit(fn)(x, w, sx, sw))
            exact = bool(np.array_equal(got, want))
            s = time_op(fn, x, w, sx, sw, reps)
            row = {"m": m, "k": k, "n": n, "blocks": label,
                   "resolved": list(ops.quant_matmul_blocks(m, k, n)
                                    if b is None else b),
                   "us": s * 1e6, "roofline_pct": 100 * floor / s,
                   "exact": exact}
            rows.append(row)
            log(json.dumps(row))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=str(ROOT / "experiments" /
                                         "quant_matmul_sweep.json"))
    args = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU; JAX found {dev.platform}", file=sys.stderr)
        return 2
    rows = sweep(log=lambda s: print(s, flush=True))
    out = {"device_kind": dev.device_kind, "rows": rows}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(out, indent=1))
    print(json.dumps({"device_kind": dev.device_kind,
                      "all_exact": all(r["exact"] for r in rows)}))
    return 0 if all(r["exact"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
