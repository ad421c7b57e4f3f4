"""Fault-tolerance + elasticity substrate (DESIGN.md §5).

On a real multi-pod deployment these hooks bind to the cluster runtime
(health RPCs, preemption notices).  The logic itself — restart bookkeeping,
straggler deadlines, elastic re-sharding decisions, gradient-skip on
divergence — is hardware-independent and fully unit-tested here on CPU
(tests/test_fault_tolerance.py).

Components:
  HeartbeatMonitor   — per-host liveness with a deadline; flags dead hosts
  StragglerPolicy    — EMA of step times; flags outlier steps/hosts and
                       recommends within-step mitigation (skip-and-average)
  ElasticPlan        — given surviving host count, proposes the new mesh and
                       whether a checkpoint reshard is needed
  TrainSupervisor    — ties it together around a training loop: run_step()
                       wrapper that checkpoints, restarts from the latest
                       committed step after a (simulated) crash, skips
                       non-finite gradient steps, and records every event

The serving engine (`repro.serving.engine`) reuses HeartbeatMonitor at
INFERENCE time: each decode slot is a "host" beating on every committed
token, with the monitor's clock bound to the engine step counter — a slot
silent for ``heartbeat_steps`` steps (a stuck fault) is quarantined and its
request requeued.  The monitor is clock-agnostic by construction
(``clock`` is injectable), which is what makes the same logic serve
wall-clock training and step-clock inference.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np


class HeartbeatMonitor:
    def __init__(self, hosts: List[str], deadline_s: float = 60.0,
                 clock: Callable[[], float] = time.monotonic):
        self.deadline = deadline_s
        self.clock = clock
        self.last_seen: Dict[str, float] = {h: clock() for h in hosts}

    def beat(self, host: str):
        self.last_seen[host] = self.clock()

    def dead_hosts(self) -> List[str]:
        now = self.clock()
        return [h for h, t in self.last_seen.items()
                if now - t > self.deadline]

    def all_alive(self) -> bool:
        return not self.dead_hosts()


class StragglerPolicy:
    """EMA-based step-time outlier detection.

    A step slower than ``threshold`` x the EMA is a straggler event; after
    ``tolerance`` consecutive events the policy recommends escalation
    (checkpoint + evict the slow host = elastic downscale)."""

    def __init__(self, threshold: float = 2.0, ema_alpha: float = 0.1,
                 tolerance: int = 3):
        self.threshold = threshold
        self.alpha = ema_alpha
        self.tolerance = tolerance
        self.ema: Optional[float] = None
        self.consecutive = 0
        self.events: List[dict] = []

    def observe(self, step: int, dt: float) -> str:
        """-> 'ok' | 'straggler' | 'escalate'."""
        if self.ema is None:
            self.ema = dt
            return "ok"
        verdict = "ok"
        if dt > self.threshold * self.ema:
            self.consecutive += 1
            verdict = ("escalate" if self.consecutive >= self.tolerance
                       else "straggler")
            self.events.append({"step": step, "dt": dt, "ema": self.ema,
                                "verdict": verdict})
        else:
            self.consecutive = 0
        # stragglers do not poison the EMA
        if verdict == "ok":
            self.ema = (1 - self.alpha) * self.ema + self.alpha * dt
        return verdict


@dataclasses.dataclass
class ElasticPlan:
    """Mesh proposal after a membership change.

    Keeps the model axis intact (TP re-layout is expensive: weights move);
    shrinks/grows the data axes, which only re-shards the FSDP dimension —
    exactly what checkpoint.restore(..., shardings=new) implements."""
    old_shape: tuple
    new_hosts: int
    chips_per_host: int = 4

    def propose(self) -> tuple:
        chips = self.new_hosts * self.chips_per_host
        model = self.old_shape[-1]
        data = max(1, chips // model)
        return (data, model)

    @property
    def needs_reshard(self) -> bool:
        return self.propose() != tuple(self.old_shape)


class TrainSupervisor:
    """Checkpoint/restart + bad-step skipping around a step function.

    step_fn(state, step) -> (state, metrics); metrics must include
    'grad_norm'.  save_fn(step, state) / restore_fn() -> (step, state) bind
    to checkpoint.py.  ``inject_crash_at`` simulates a node failure for
    tests."""

    def __init__(self, step_fn, save_fn, restore_fn, ckpt_every: int = 50,
                 max_bad_steps: int = 5, inject_crash_at: Optional[int] = None):
        self.step_fn = step_fn
        self.save_fn = save_fn
        self.restore_fn = restore_fn
        self.ckpt_every = ckpt_every
        self.max_bad = max_bad_steps
        self.inject_crash_at = inject_crash_at
        self.log: List[dict] = []
        self.straggler = StragglerPolicy()

    def run(self, total_steps: int):
        step, state = self.restore_fn()
        bad = 0
        crashed = False
        while step < total_steps:
            t0 = time.monotonic()
            if self.inject_crash_at is not None and step == self.inject_crash_at \
                    and not crashed:
                crashed = True
                self.log.append({"event": "crash", "step": step})
                step, state = self.restore_fn()   # restart from checkpoint
                continue
            new_state, metrics = self.step_fn(state, step)
            gn = float(metrics.get("grad_norm", 0.0))
            if not np.isfinite(gn):
                bad += 1
                self.log.append({"event": "skip_nonfinite", "step": step})
                if bad > self.max_bad:
                    raise RuntimeError("too many non-finite steps")
                step += 1          # skip the update, keep the old state
                continue
            bad = 0
            state = new_state
            verdict = self.straggler.observe(step, time.monotonic() - t0)
            if verdict != "ok":
                self.log.append({"event": verdict, "step": step})
            step += 1
            if step % self.ckpt_every == 0:
                self.save_fn(step, state)
        self.save_fn(step, state)
        return step, state
