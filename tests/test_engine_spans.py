"""Engine phase spans and counters (`Engine._span`, ``stats``,
`RequestResult.queue_s`): what each path records, that the splits nest
inside the intervals they split, and that a profiler trace carries every
span on the host plane with each child inside its parent."""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import base as cfgbase
from repro.models import transformer as T
from repro.runtime import PlanSet, lower
from repro.serving import Engine, Request
from repro.serving.engine import span_key

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
import devtrace  # noqa: E402

# span -> its parent (the tree in the `repro.serving.engine` docstring)
PARENT = {
    "engine.run": None,
    "engine.step": "engine.run",
    "engine.schedule": "engine.step",
    "engine.admit": "engine.step",
    "engine.chunk": "engine.step",
    "engine.chunk.dispatch": "engine.chunk",
    "engine.chunk.wait": "engine.chunk",
    "engine.decode": "engine.step",
    "engine.decode.dispatch": "engine.decode",
    "engine.decode.wait": "engine.decode",
    "engine.decode.commit": "engine.decode",
}
# the method each path runs once per decode step, and its first argument
# after ``self`` is the batch
DECODE_STEP = {"paged": "_decode_groups", "dense": "_dense_decode",
               "spec": "_spec_round"}
PATHS = tuple(DECODE_STEP)


def _requests(lens, new, arrival=0):
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=rng.integers(0, 50, n).astype(np.int32),
                    max_new_tokens=m, arrival_step=arrival)
            for i, (n, m) in enumerate(zip(lens, new))]


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    cfgbase.load_all()
    cfg = cfgbase.reduce_for_smoke(cfgbase.get("zamba2-1.2b"))
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    return cfg, params, tmp_path_factory.mktemp("spans")


def _engine(model, path, max_batch=4):
    cfg, params, tmp = model
    if path == "spec":
        from repro.launch.train import emit_static_mapping
        art = emit_static_mapping(params, cfg, "diana", tmp / "m.json",
                                  act_log_scale=2.0)
        bank = PlanSet({"target": lower(art, params=params),
                        "draft": lower(art, params=params)},
                       params, default="target")
        return Engine(cfg, params, max_batch=max_batch, max_len=64,
                      backend=bank, speculate=("draft", "target"),
                      draft_k=2)
    return Engine(cfg, params, max_batch=max_batch, max_len=64,
                  kv_layout=path)


@pytest.fixture(scope="module")
def runs(model):
    """Each path serving one fixed schedule, with the active slots of each
    decode call recorded beside the engine's own count."""
    out = {}
    for path in PATHS:
        eng = _engine(model, path)
        seen = []
        orig = getattr(eng, DECODE_STEP[path])

        def record(batch, *a, _orig=orig, _seen=seen, **k):
            _seen.append(batch.n_active)
            return _orig(batch, *a, **k)

        setattr(eng, DECODE_STEP[path], record)
        results = eng.run(_requests([5, 9, 3, 7, 6, 4], [6, 3, 8, 5, 2, 4]))
        out[path] = (eng, dict(eng.stats), results, seen)
    return out


@pytest.mark.parametrize("path", PATHS)
def test_decode_rows_sum_the_active_slots_of_each_call(runs, path):
    _, stats, results, seen = runs[path]
    assert len(seen) == stats["decode_steps"] > 0
    assert stats["decode_rows"] == sum(seen)
    if path != "spec":      # one token per active slot and decode call
        assert stats["decode_rows"] == sum(len(r.tokens) - 1
                                           for r in results)


@pytest.mark.parametrize("path", PATHS)
def test_splits_lie_inside_the_intervals_they_split(runs, path):
    _, s, _, _ = runs[path]
    assert s["engine_decode_dispatch_s"] + s["engine_decode_wait_s"] <= \
        s["decode_s"]
    assert s["engine_chunk_dispatch_s"] + s["engine_chunk_wait_s"] <= \
        s["prefill_s"]
    assert s["decode_s"] <= s["engine_decode_s"]
    assert s["engine_step_s"] <= s["engine_run_s"] <= s["wall_s"]
    for name, parent in PARENT.items():
        assert s[span_key(name)] >= 0
        if parent is not None:
            assert s[span_key(name)] <= s[span_key(parent)]


def test_every_path_fills_the_same_keys(runs):
    keys = {p: {k for k in runs[p][1] if k.startswith("engine_")}
            for p in PATHS}
    assert keys["paged"] == {span_key(n) for n in PARENT}
    assert keys["dense"] == keys["paged"] == keys["spec"]
    assert "straggler_events" not in runs["paged"][1]


@pytest.mark.parametrize("path", PATHS)
def test_queue_wait_ends_at_first_admission(runs, path):
    _, _, results, _ = runs[path]
    for r in results:
        assert 0 <= r.queue_s <= r.ttft_s
    # four slots, six requests at step 0: the first four take free slots
    # at once, the last two wait for one of them to finish
    first = min(r.finish_s for r in results[:4])
    assert max(r.queue_s for r in results[:4]) < 0.1 * first
    assert min(r.queue_s for r in results[4:]) >= first


@pytest.mark.parametrize("path", ["paged", "dense"])
def test_one_slot_queues_the_second_request_behind_the_first(model, path):
    first, second = _engine(model, path, max_batch=1).run(
        _requests([5, 6], [4, 3]))
    assert first.queue_s < 0.1 * first.finish_s
    assert second.queue_s >= first.finish_s
    assert second.queue_s <= second.ttft_s


def test_profiler_trace_holds_every_span_inside_its_parent(runs, tmp_path):
    eng = runs["paged"][0]
    with jax.profiler.trace(str(tmp_path)):
        eng.run(_requests([5, 40, 3], [3, 2, 4]))
    events = [e for e in devtrace.load(str(tmp_path)).host
              if e.name.startswith("engine.")]
    by_name = {}
    for e in events:
        by_name.setdefault(e.name, []).append(e)
    assert set(by_name) == set(PARENT)
    for e in events:
        parent = PARENT[e.name]
        if parent is None:
            continue
        assert any(p.start_ns <= e.start_ns and e.end_ns <= p.end_ns
                   for p in by_name[parent]), e
