"""Reduce a profiler trace of the measured window to device metrics.

The reduction works on plain event tuples, so a test can feed it a small
recorded or made-up trace; `load` adapts a JAX ``.xplane.pb`` file to them.
Device events are the ``XLA Ops`` line of each ``/device:`` plane; host
events are every line of the ``/host:`` planes (the harness's own
``TraceAnnotation`` spans and the runtime's dispatch events among them).
"""
from __future__ import annotations

import dataclasses
import glob
from typing import Dict, List, Tuple

DEVICE_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str               # on a TPU, the HLO instruction's text
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    device: Dict[str, List[Event]]   # device plane name -> op events
    host: List[Event]


def load(directory: str) -> Trace:
    """Read the one ``.xplane.pb`` the profiler wrote under ``directory``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(paths[-1])
    device: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            evs = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != DEVICE_LINE:
                    continue
                for ev in line.events:
                    evs.append(Event(ev.name, ev.start_ns, ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.append(Event(ev.name, ev.start_ns, ev.duration_ns))
    return Trace(device={k: v for k, v in device.items() if v}, host=host)


def clip(events: List[Event], t0: float, t1: float) -> List[Event]:
    """The parts of ``events`` inside [t0, t1]."""
    out = []
    for e in events:
        s, f = max(e.start_ns, t0), min(e.end_ns, t1)
        if f > s:
            out.append(Event(e.name, s, f - s))
    return out


def busy_intervals(events: List[Event]) -> List[Tuple[float, float]]:
    """The union of the events' intervals, merged and sorted."""
    spans = sorted((e.start_ns, e.end_ns) for e in events)
    out: List[List[float]] = []
    for s, f in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], f)
        else:
            out.append([s, f])
    return [(s, f) for s, f in out]


def busy_ns(events: List[Event]) -> float:
    return sum(f - s for s, f in busy_intervals(events))


def idle_gaps(events: List[Event], t0: float, t1: float):
    """(start, end) of every stretch of [t0, t1] with no device op."""
    gaps, t = [], t0
    for s, f in busy_intervals(events):
        if s > t:
            gaps.append((t, s))
        t = max(t, f)
    if t1 > t:
        gaps.append((t, t1))
    return gaps


def host_activity(host: List[Event], t: float) -> str:
    """The shortest host span that covers time ``t``: what the host was
    doing then, as precisely as the trace says."""
    best = None
    for e in host:
        if e.start_ns <= t < e.end_ns and (best is None
                                           or e.dur_ns < best.dur_ns):
            best = e
    return best.name if best is not None else "no host span"


CONTAINERS = (" while(", " conditional(", " call(")


def short_name(name: str) -> str:
    """``%fusion.5 = bf16[8,128]{1,0:T(8,128)} fusion(...)`` -> ``fusion.5
    bf16[8,128]``: the HLO instruction and its result type, as the device
    line of a TPU trace names its events."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    lhs = lhs.lstrip("%")
    if rhs.startswith("("):
        return lhs
    return f"{lhs} {rhs.split(' ', 1)[0].split('{', 1)[0]}"


def top_ops(events: List[Event], k: int = 10):
    """[[name, seconds]] of the ``k`` ops with the most device time.  Loop
    and call ops are left out: their time is their body's ops."""
    tot: Dict[str, float] = {}
    for e in events:
        if any(c in e.name for c in CONTAINERS):
            continue
        n = short_name(e.name)
        tot[n] = tot.get(n, 0.0) + e.dur_ns
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns * 1e-9] for n, ns in best]


def top_gaps(events: List[Event], host: List[Event], t0: float, t1: float,
             k: int = 10):
    """[[host activity, seconds]] of the ``k`` longest idle gaps."""
    gaps = sorted(idle_gaps(events, t0, t1), key=lambda g: g[0] - g[1])[:k]
    return [[host_activity(host, (s + f) / 2), (f - s) * 1e-9]
            for s, f in gaps]


def kernel_ns(events: List[Event], needle: str) -> Tuple[float, int]:
    """(device ns, event count) of the custom-call events that name
    ``needle``: a Pallas kernel is a ``custom-call`` instruction named after
    the jitted op that launches it (``%quant_matmul_op.12 = ...
    custom-call(...)``)."""
    hits = [e for e in events if needle in e.name.split(" = ", 1)[0]
            and " custom-call(" in e.name]
    return sum(e.dur_ns for e in hits), len(hits)
