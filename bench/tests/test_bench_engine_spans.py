"""The readers of the engine's phase spans and counters on a CPU smoke run:
each reports, in range.  These are program-side counts and host times on
the CPU, not device numbers."""
import pytest

from test_bench_harness import make_root, run

SPAN_METRICS = ("host_loop_share", "decode_dispatch_ms", "decode_occupancy",
                "queue_wait_p90_ms")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    return run(make_root(tmp_path_factory.mktemp("spans")), trace=True)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_traced_smoke_run_reports_span_metric(traced, name):
    assert traced["correct"] is True
    m = traced["metrics"][name]
    if name == "decode_occupancy":
        assert 0 < m["value"] <= 100 and m["unit"] == "%"
    elif name == "host_loop_share":
        assert 0 <= m["value"] <= 100 and m["unit"] == "%"
    else:
        assert m["value"] >= 0 and m["unit"] == "ms"


def test_readers_stay_silent_without_the_program_counters():
    """A program without the spans (stats and results lacking them) gives
    no reading rather than an error."""
    import harness
    from types import SimpleNamespace
    ctx = {"stats": {"wall_s": 1.0, "prefill_s": 0.2, "decode_s": 0.5,
                     "decode_steps": 10, "prefill_calls": 2},
           "done": [SimpleNamespace(rid=0, ttft_s=0.1)],
           "mix": {"engine": {"max_batch": 4}}}
    for name in SPAN_METRICS:
        assert harness.metric_reader(name).read(ctx) is None
