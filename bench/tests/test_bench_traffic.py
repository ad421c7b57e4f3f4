"""The seeded traffic generator: determinism, the same work for every
seed, the knee, and the bursts of the arrival schedule."""
import json
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import traffic  # noqa: E402

MIX = json.loads((BENCH / "traffic" / "chat.json").read_text())


def _sig(draws):
    return [(d.rid, d.prompt.tobytes(), d.max_new_tokens, d.arrival_step)
            for d in draws]


def test_same_seed_same_round():
    a = traffic.make_round(MIX, 32000, 2 ** 33 + 5, 3)
    b = traffic.make_round(MIX, 32000, 2 ** 33 + 5, 3)
    assert _sig(a) == _sig(b)
    assert _sig(a) != _sig(traffic.make_round(MIX, 32000, 2 ** 33 + 6, 3))
    assert _sig(a) != _sig(traffic.make_round(MIX, 32000, 2 ** 33 + 5, 4))


def test_every_seed_gets_the_same_schedule():
    ref = None
    for seed in (1, 77, 2 ** 31 + 3):
        r = traffic.make_round(MIX, 32000, seed, 0)
        work = [(len(d.prompt), d.max_new_tokens, d.arrival_step) for d in r]
        ref = ref or work
        assert work == ref
        assert all(0 <= t < 32000 for d in r for t in d.prompt)
    n = MIX["round"]["requests"]
    assert len(ref) == n
    assert sum(a == 0 for _, _, a in ref) == round(
        MIX["round"]["initial_share"] * MIX["engine"]["max_batch"])
    # the next round holds the same lengths in another order
    nxt = traffic.make_round(MIX, 32000, 1, 1)
    assert Counter(len(d.prompt) for d in nxt) == Counter(p for p, _, _ in ref)
    assert [len(d.prompt) for d in nxt] != [p for p, _, _ in ref]


def test_knee_from_the_drawn_lengths():
    prompts, outputs = traffic.round_sizes(MIX)
    assert prompts.min() >= MIX["prompt"]["min"]
    assert prompts.max() <= MIX["prompt"]["max"]
    chunks = np.ceil(prompts / MIX["engine"]["prefill_chunk"]).mean()
    assert traffic.knee(MIX) == pytest.approx(
        MIX["engine"]["max_batch"] / (outputs.mean() + chunks))
    assert 0.05 < traffic.knee(MIX) < 0.3


def test_bursts_warp_the_arrival_clock():
    # rate 1 per step, doubled in the first 10 steps and halved after
    steps = traffic._warp(np.array([10.0, 20.0, 25.0]), 1.0, 10,
                          [2.0, 0.5])
    assert steps.tolist() == pytest.approx([5.0, 10.0, 20.0])
