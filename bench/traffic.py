"""Seeded request streams drawn from one traffic mix.

A mix is a JSON file of parameters (``bench/traffic/<name>.json``); this is
the one generator that reads them.  A round's prompt lengths, output
lengths and arrival gaps are stratified quantiles of the mix's
distributions, put in an order drawn from the round's index alone; the seed
draws the token ids.  A window serves several rounds, each in its own
order, and every seed serves the same sequence of them, so the amount and
order of work do not change with the seed: when the seed also drew the
order of a window's one round, which long request landed in the step-0
burst moved its p90 time to first token by a factor of 2.4 (2588 to 6249
ms over 6 seeds, a hybrid Mamba2 model on a TPU v5 lite).  Arrival gaps
are the quantiles of an exponential, so arrivals are Poisson stratified:
each round holds the same set of gaps.

Schema (every key required unless marked optional)::

    engine   {max_batch, prefill_chunk, page_size, max_len[, num_pages]}
                                           the engine's slots and KV pages
                                           (num_pages optional: the pool
                                           defaults to max_batch full slots)
    prompt   {median, sigma, min, max}     lognormal prompt tokens, clipped
    output   {median, sigma, min, max}     lognormal output tokens, clipped
    round    {requests, initial_share}     requests per round; the first
                                           round(initial_share * max_batch)
                                           arrive at step 0
    arrivals {load, period_steps, factors} Poisson in engine steps at
                                           load * knee requests per step;
                                           the rate is multiplied by
                                           factors[k % len(factors)] in the
                                           k-th period of period_steps steps
    check    {sample_tokens}               served tokens the correctness
                                           check compares, at least
    source   text (optional)               where the numbers come from
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Draw:
    """One request of a round: its sizes, its arrival step, its prompt."""
    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    arrival_step: int


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """The (i + 0.5) / n quantiles of the clipped lognormal ``spec``."""
    nd = NormalDist()
    mu, sigma = math.log(spec["median"]), float(spec["sigma"])
    vals = [math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n)) for i in range(n)]
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def exponential_quantiles(n: int) -> np.ndarray:
    """The (i + 0.5) / n quantiles of a unit-mean exponential."""
    return np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])


def round_sizes(mix: dict):
    """(prompt lengths, output lengths) of one round, in stratified order."""
    n = int(mix["round"]["requests"])
    return lognormal_quantiles(mix["prompt"], n), \
        lognormal_quantiles(mix["output"], n)


def knee(mix: dict) -> float:
    """Requests per engine step at which every slot is busy: slots over
    the steps one request holds a slot (its output tokens plus its prefill
    chunks).  It depends only on the slots and the lengths, not on speed."""
    prompts, outputs = round_sizes(mix)
    chunks = np.ceil(prompts / mix["engine"]["prefill_chunk"])
    return mix["engine"]["max_batch"] / float(outputs.mean() + chunks.mean())


def _warp(cum: np.ndarray, rate: float, period: int, factors) -> np.ndarray:
    """Steps at which a process of rate ``rate * factors[k]`` in its k-th
    period of ``period`` steps has made ``cum`` expected arrivals."""
    out = np.empty(len(cum))
    t, done, k = 0.0, 0.0, 0
    for i, target in enumerate(cum):
        while True:
            r = rate * factors[k % len(factors)]
            end = (k + 1) * period
            room = (end - t) * r
            if done + room >= target:
                t += (target - done) / r
                done = target
                break
            done += room
            t = float(end)
            k += 1
        out[i] = t
    return out


def make_round(mix: dict, vocab: int, seed: int, index: int) -> List[Draw]:
    """Round ``index`` of the stream of ``seed``."""
    order = np.random.default_rng(int(index))
    prompts, outputs = round_sizes(mix)
    n = len(prompts)
    prompts = prompts[order.permutation(n)]
    outputs = outputs[order.permutation(n)]
    n0 = min(n, int(round(mix["round"]["initial_share"]
                          * mix["engine"]["max_batch"])))
    arr = mix["arrivals"]
    rate = arr["load"] * knee(mix)
    gaps = exponential_quantiles(n - n0)[order.permutation(n - n0)]
    steps = _warp(np.cumsum(gaps), rate, int(arr["period_steps"]),
                  [float(f) for f in arr["factors"]])
    arrival = np.concatenate([np.zeros(n0), np.floor(steps)]).astype(int)
    rng = np.random.default_rng([int(seed), int(index)])
    return [Draw(rid=index * n + i,
                 prompt=rng.integers(0, vocab, int(prompts[i]),
                                     dtype=np.int32),
                 max_new_tokens=int(outputs[i]),
                 arrival_step=int(arrival[i])) for i in range(n)]
