"""One run of one benchmark cell: set-up, measured window, check, metrics.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives it:

  configuration   ``file`` of its ``configs`` entry (JSON: the program's
                  arch name, the sizes as run, the served precision, the
                  reference module and the correctness limit)
  traffic mix     ``bench/traffic/<traffic>.json`` (see `bench.traffic`)
  per-layer       ``bench/metrics/<name>.py`` with ``read(ctx)`` returning
  metric          a number, or None where it finds nothing to read

`run_cell` holds the phases without the check for a chip, so that a test
can drive a whole run at a small size on the CPU.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import check as check_mod                        # noqa: E402
import roofline                                  # noqa: E402
import stats                                     # noqa: E402
import devtrace as trace_mod                     # noqa: E402
import traffic                                   # noqa: E402
from weights import make_params                  # noqa: E402

WINDOW_SPAN = "bench.window"


class BenchError(RuntimeError):
    """The run cannot be measured as the cell states."""


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def find_cell(bench: dict, name: str, root: Path = ROOT):
    """(workload, configuration file, traffic mix)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(root / entry["file"])
    mix = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    return cell, config, mix


def _load_module(path: Path, tag: str):
    spec = importlib.util.spec_from_file_location(tag, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT):
    return _load_module(root / "bench" / "metrics" / f"{name}.py",
                        f"bench_metric_{name.replace('.', '_')}")


class CompileCounter:
    """Counts lowerings and backend compiles while armed."""

    def __init__(self):
        import jax
        self.armed = False
        self.counts = {"lowerings": 0, "compiles": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if not self.armed:
            return
        if event.endswith("jaxpr_to_mlir_module_duration"):
            self.counts["lowerings"] += 1
        elif event.endswith("backend_compile_duration"):
            self.counts["compiles"] += 1


def program_cfg(config: dict):
    """The program's ArchConfig for the configuration file's sizes."""
    from repro.configs import base as cfgbase
    cfgbase.load_all()
    base = cfgbase.get(config["arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    upd = {k: (tuple(v) if isinstance(v, list) else v)
           for k, v in config["sizes"].items() if k in fields}
    return dataclasses.replace(base, **upd)


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def _leaf_paths(tree, is_leaf=None) -> dict:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_leaf)
    return {jax.tree_util.keystr(p, simple=True, separator="/"): leaf
            for p, leaf in flat}


def check_shapes(shapes, sizes: dict, reference):
    """The weights the program asks for are those the reference reads, at
    its widths: every leaf path and shape of ``shapes`` (the program's
    tree, from `jax.eval_shape`) against ``reference.param_shapes(sizes)``."""
    if not hasattr(reference, "param_shapes"):
        raise BenchError(f"reference {reference.__name__} has no "
                         f"param_shapes(sizes) to declare its widths")
    got = {p: tuple(s.shape) for p, s in _leaf_paths(shapes).items()}
    want = _leaf_paths(reference.param_shapes(sizes), _is_shape)
    bad = [p for p in sorted(got.keys() | want.keys())
           if got.get(p) != want.get(p)]
    if bad:
        raise BenchError(
            f"program weights unlike the reference's ({len(bad)}): " +
            "; ".join(
                f"{p} wants {want.get(p, 'no leaf')}, got "
                f"{got.get(p, 'no leaf')}" for p in bad[:8]))


def _requests(draws):
    from repro.serving import Request
    return [Request(rid=d.rid, prompt=d.prompt,
                    max_new_tokens=d.max_new_tokens,
                    arrival_step=d.arrival_step) for d in draws]


def _warm_rounds(mix: dict, vocab: int):
    """Warm-up rounds.  The first holds one request whose prompt takes two
    chunks: it compiles the chunk step for a fresh cache and for one a
    chunk has written, and the decode step.  Each later one admits k
    one-token requests at once, which compiles the slot reset for k slots,
    for the k the window's admissions take: 1 to 4, and the share of the
    slots that arrive at step 0."""
    eng = mix["engine"]
    rng = np.random.default_rng(0)
    draw = lambda i, n, new: traffic.Draw(
        rid=-1 - i, prompt=rng.integers(0, vocab, n, dtype=np.int32),
        max_new_tokens=new, arrival_step=0)
    n0 = int(round(mix["round"]["initial_share"] * eng["max_batch"]))
    rounds = [[draw(0, eng["prefill_chunk"] + 1, 2)]]
    for k in sorted({1, 2, 3, 4, n0} & set(range(1, eng["max_batch"] + 1))):
        rounds.append([draw(i, 8, 1) for i in range(k)])
    return rounds


def run_cell(bench: dict, name: str, *, seed: int, seconds: float,
             trace: bool, log, t_start: float, root: Path = ROOT,
             keep: dict | None = None):
    """Set up, measure and check one run; returns the result dict.
    ``keep``, when given, receives what the check compared (weights,
    sizes, reference module, sampled requests and prompts)."""
    import jax
    from repro.launch import serve
    from repro.launch.train import emit_static_mapping
    from repro.models import transformer as T
    from repro.runtime import PlannedBackend, lower
    from repro.serving import Engine, RequestResult

    _, config, mix = find_cell(bench, name, root)
    sizes, served = config["sizes"], config["serving"]
    phases = {}

    def phase(tag, t):
        phases[tag] = time.perf_counter() - t
        log(f"phase {tag}: {phases[tag]:.3f} s")

    # ---- set-up ------------------------------------------------------
    t = time.perf_counter()
    cfg = program_cfg(config)
    shapes = jax.eval_shape(lambda: T.init_lm(jax.random.PRNGKey(0), cfg))
    reference = check_mod.load_reference(root / config["reference"])
    check_shapes(shapes, sizes, reference)
    params = jax.block_until_ready(make_params(shapes, seed))
    phase("weights", t)

    t = time.perf_counter()
    with tempfile.TemporaryDirectory() as tmp:
        art = emit_static_mapping(params, cfg, served["platform"],
                                  Path(tmp) / "mapping.json",
                                  act_log_scale=served["act_log_scale"])
    phase("emit", t)

    t = time.perf_counter()
    plan = lower(art, params=params)
    backend = PlannedBackend(plan, params)
    if backend.unbound:
        raise BenchError(f"{len(backend.unbound)} planned layers unbound: "
                         f"{backend.unbound[:8]}")
    hist = plan.kernel_histogram()
    bits = [int(d["weight_bits"]) for d in plan.domains]
    layers = [(lp.name, lp.kernel, lp.c_in, lp.c_out,
               sum(c for c, b in zip(lp.counts, bits) if b <= 8) / lp.c_out)
              for lp in plan.layers]
    serve_cfg = serve.planned_kv_cfg(cfg, art)
    log(f"plan: {len(layers)} layers bound, 0 unbound, kernels {hist}, "
        f"kv cache {serve_cfg.kv_cache_dtype}")
    if set(hist) != set(served["kernels"]):
        raise BenchError(f"plan kernels {hist}, the configuration serves "
                         f"{served['kernels']}")
    eng_cfg = mix["engine"]
    engine = Engine(serve_cfg, params, backend=backend,
                    max_batch=eng_cfg["max_batch"],
                    max_len=eng_cfg["max_len"],
                    page_size=eng_cfg["page_size"],
                    prefill_chunk=eng_cfg["prefill_chunk"],
                    num_pages=eng_cfg.get("num_pages"))
    phase("bind", t)

    t = time.perf_counter()
    for draws in _warm_rounds(mix, cfg.vocab):
        engine.run(_requests(draws))
    phase("warm", t)
    if backend.runtime_declines:
        raise BenchError(f"planned layers declined at trace time: "
                         f"{sorted(backend.runtime_declines)}")
    counter = CompileCounter()

    # ---- window ------------------------------------------------------
    tracedir = tempfile.TemporaryDirectory() if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # runtime host events, no Python
        jax.profiler.start_trace(tracedir.name, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    counter.armed = True
    results, prompts, want_new = [], {}, {}
    totals: dict = {}
    rounds = 0
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        while True:
            draws = traffic.make_round(mix, cfg.vocab, seed, rounds)
            for d in draws:
                prompts[d.rid] = d.prompt
                want_new[d.rid] = d.max_new_tokens
            with jax.profiler.TraceAnnotation(f"bench.round {rounds}"):
                results += engine.run(_requests(draws))
            for k, v in engine.stats.items():
                if isinstance(v, (int, float)):
                    totals[k] = totals.get(k, 0) + v
            rounds += 1
            if time.perf_counter() - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    counter.armed = False
    if trace:
        jax.profiler.stop_trace()
    log(f"window: {rounds} rounds, {len(results)} requests, "
        f"{window_s:.3f} s; in the window {counter.counts['lowerings']} "
        f"lowerings, {counter.counts['compiles']} compiles")

    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    peak = mem.get("peak_bytes_in_use")
    done = [r for r in results if isinstance(r, RequestResult)
            and r.finish_reason == "max_new_tokens"
            and len(r.tokens) == want_new[r.rid]
            and all(0 <= tk < cfg.vocab for tk in r.tokens)]
    declines = len(backend.runtime_declines)

    # ---- check: free the program's state, then the reference ---------
    picked = check_mod.sample(done, prompts, seed,
                              mix["check"]["sample_tokens"])
    del engine, backend, plan
    gc.collect()
    jax.clear_caches()
    t = time.perf_counter()
    gap_rows = check_mod.gaps(reference, params, sizes, picked, prompts)
    widest = float(max(g.max() for g in gap_rows)) if gap_rows else \
        float("inf")
    log(f"check: {len(picked)} requests, "
        f"{sum(len(g) for g in gap_rows)} served tokens against the "
        f"reference in {time.perf_counter() - t:.3f} s")
    if keep is not None:
        keep.update(params=params, sizes=sizes, reference=reference,
                    picked=picked, prompts=prompts, gaps=gap_rows,
                    config=config, done=done, window_s=window_s,
                    stats=totals, rounds=rounds)
    checks = {
        "max_logit_gap": {"value": widest,
                          "limit": config["correct"]["max_logit_gap"]},
        "incomplete": {"value": len(results) - len(done), "limit": 0},
        "declined_layers": {"value": declines, "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    # ---- metrics -----------------------------------------------------
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(results),
           "failed": len(results) - len(done)}
    ctx = {"stats": totals, "window_s": window_s, "done": done,
           "prompts": prompts, "mix": mix, "sizes": sizes, "params": params,
           "layers": layers, "reference": reference, "trace": None,
           "peaks": (roofline.peaks_for(dev.device_kind)
                     if dev.platform == "tpu" else None)}
    if trace:
        ctx["trace"] = _reduce_trace(tracedir.name, log)
        tracedir.cleanup()
    wanted = bench["per_layer"] if trace else bench["end_to_end"]
    values = {}
    if trace:
        for m in wanted:
            if name not in m.get("workloads", [name]):
                continue
            v = metric_reader(m["name"], root).read(ctx)
            if v is not None:
                values[m["name"]] = v
    else:
        e2e = stats.serving_metrics(done, window_s) if done else {}
        e2e["peak_hbm_gb"] = peak / 1e9 if peak is not None else None
        e2e["setup_s"] = setup_s
        for m in wanted:
            if name in m.get("workloads", [name]) and \
                    e2e.get(m["name"]) is not None:
                values[m["name"]] = e2e[m["name"]]
    units = {m["name"]: m["unit"] for m in wanted}
    out["metrics"] = {k: {"value": v, "unit": units[k]}
                      for k, v in values.items()}
    out["device"] = device
    if trace and ctx["trace"] is not None:
        tr = ctx["trace"]
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        out["breakdown"] = {"device_ops": tr["top_ops"],
                            "idle_gaps": tr["top_gaps"]}
    out["phases_s"] = phases
    out["checks"] = checks
    return out


def _reduce_trace(directory: str, log):
    """Device busy time, top ops and idle gaps of the traced window (the
    harness's window span), averaged over the chips that ran ops."""
    t = time.perf_counter()
    tr = trace_mod.load(directory)
    span = [e for e in tr.host if e.name == WINDOW_SPAN]
    if not span or not tr.device:
        log("trace: no window span or no device ops")
        return None
    t0, t1 = span[0].start_ns, span[0].end_ns
    per_chip = {k: trace_mod.clip(v, t0, t1) for k, v in tr.device.items()}
    busy = [trace_mod.busy_ns(v) for v in per_chip.values()]
    first = per_chip[sorted(per_chip)[0]]
    log(f"trace: {sum(len(v) for v in per_chip.values())} device ops on "
        f"{len(per_chip)} chips, {len(tr.host)} host events, read in "
        f"{time.perf_counter() - t:.3f} s")
    return {"busy_s": sum(busy) / len(busy) * 1e-9,
            "window_s": (t1 - t0) * 1e-9, "events": first,
            "top_ops": trace_mod.top_ops(first),
            "top_gaps": trace_mod.top_gaps(first, tr.host, t0, t1)}


def main(argv=None, t_start: float | None = None):
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    log = lambda s: print(f"[bench] {s}", file=sys.stderr, flush=True)

    bench = load_json(ROOT / "BENCHMARK.json")
    cell, _, _ = find_cell(bench, args.workload)
    import jax
    if jax.default_backend() != "tpu":
        log(f"needs a TPU; JAX found {jax.default_backend()!r}")
        return 2
    if len(jax.devices()) < cell["chips"]:
        log(f"cell asks for {cell['chips']} chips; JAX found "
            f"{len(jax.devices())}")
        return 2
    dev = jax.devices()[0]
    try:
        roofline.peaks_for(dev.device_kind)
    except roofline.UnknownDevice as e:
        log(str(e))
        return 2
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    log(f"device {dev.device_kind} x{len(jax.devices())}; compile cache "
        f"{cache}")
    out = run_cell(bench, args.workload, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace), log=log,
                   t_start=t_start)
    for k, c in out["checks"].items():
        print(f"[bench] check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
