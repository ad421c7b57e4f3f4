#!/usr/bin/env python3
"""The precision control of a cell, read beside the program, seed by seed.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> ...

For each seed, one whole run of the cell (set-up, window, check) and then
the reference computed in the next precision down from the one the
configuration serves (``correct.control_bits``, for the weights and the
inputs of every projection) over the same prompts and served tokens: the
widest gap below the reference's best logit of the token the control ranks
first.
One JSON line per seed gives the program's reading, the control's, and the
limit between them.  The benchmark's own runs do not run this; it is how the
limit in the configuration file was set, and `tests/test_bench_control.py`
runs it at a size a test can hold.
"""
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import check                                    # noqa: E402
import harness                                  # noqa: E402


def readings(bench, cell, seeds, seconds, *, root=harness.ROOT,
             log=lambda s: None):
    """[{seed, program, control, limit, tokens, metrics, phases_s}] for
    each seed: the readings, the served tokens compared, and the run's
    end-to-end metrics and set-up phases."""
    out = []
    for seed in seeds:
        keep = {}
        res = harness.run_cell(bench, cell, seed=seed, seconds=seconds,
                               trace=False, log=log,
                               t_start=time.perf_counter(), root=root,
                               keep=keep)
        cfg = keep["config"]
        low = check.gaps(keep["reference"], keep["params"], keep["sizes"],
                         keep["picked"], keep["prompts"],
                         control=(cfg["correct"]["control_bits"],
                                  cfg["serving"]["act_log_scale"]))
        out.append({"seed": seed,
                    "program": res["checks"]["max_logit_gap"]["value"],
                    "control": float(max(g.max() for g in low)),
                    "limit": cfg["correct"]["max_logit_gap"],
                    "tokens": int(sum(len(g) for g in low)),
                    "metrics": {k: m["value"]
                                for k, m in res["metrics"].items()},
                    "phases_s": res["phases_s"]})
        keep.clear()
    return out


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    if jax.default_backend() != "tpu":
        print(f"[control] needs a TPU; JAX found {jax.default_backend()!r}",
              file=sys.stderr)
        return 2
    cache = harness.os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(harness.ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    log = lambda s: print(f"[control] {s}", file=sys.stderr, flush=True)
    for r in readings(bench, args.workload, args.seeds, args.seconds,
                      log=log):
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
