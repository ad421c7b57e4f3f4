"""The comparison that decides ``correct`` for a served model.

Once the window has closed, a sample of the completed requests, drawn from
the seed and holding the one with the longest sequence, is run through the
plain reference once each: its prompt followed by the tokens the engine
served.  At every served position the reference's logits say how far the
served token lies below the reference's best token; the widest such gap
over the sample is the number compared.  The served tokens are greedy, so a
sound engine only ever picks a token that its own rounding lifted to the
top.  The precision control reads the same gap for the token that the
reference computed in the next precision down puts first.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

PAD = 256           # sequences are padded to a multiple of this
ROW_PAD = 128       # compared rows are padded to a multiple of this


def load_reference(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_reference_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sample(done, prompts, seed: int, min_tokens: int):
    """Completed requests to compare: the longest sequence first, then
    others in an order drawn from the seed, until ``min_tokens`` served
    tokens are covered."""
    if not done:
        return []
    size = lambda r: len(prompts[r.rid]) + len(r.tokens)
    longest = max(done, key=size)
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 104729])
    picked, total = [longest], len(longest.tokens)
    for i in rng.permutation(len(rest)):
        if total >= min_tokens:
            break
        picked.append(rest[i])
        total += len(rest[i].tokens)
    return picked


def _pad(n: int, to: int) -> int:
    return -(-n // to) * to


def gaps(reference, params, sizes, picked, prompts, *, control=None):
    """Per-request arrays of the gap below the reference's best logit: of
    the served token, or with ``control=(bits, act_log_scale)`` of
    the token the control ranks first."""
    length = _pad(max(len(prompts[r.rid]) + len(r.tokens) - 1
                      for r in picked), PAD)
    out = []
    for r in picked:
        prompt = np.asarray(prompts[r.rid], np.int32)
        served = np.asarray(r.tokens, np.int32)
        seq = np.zeros(length, np.int32)
        seq[:len(prompt)] = prompt
        seq[len(prompt):len(prompt) + len(served) - 1] = served[:-1]
        n = len(served)
        rows = np.full(_pad(n, ROW_PAD), len(prompt) - 1, np.int32)
        rows[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        ref = np.asarray(reference.logits(params, sizes, seq, rows))[:n]
        if control is None:
            pick = served
        else:
            bits, act_log_scale = control
            low = np.asarray(reference.logits(
                params, sizes, seq, rows, bits=bits,
                act_log_scale=act_log_scale))[:n]
            pick = low.argmax(axis=-1)
        out.append(ref.max(axis=-1) - ref[np.arange(n), pick])
    return out
