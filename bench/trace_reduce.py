"""The reduction from a profiler trace to device busy time, the idle share
and the breakdown.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with JAX
alone: per TPU, the events of its op line (``XLA Ops``; where a device has
none, its ``XLA Modules`` line) and of its module line; and every host
event. ``reduce`` takes those lists, so a test can hand it a small
recorded or synthetic trace:

* busy: per device, the union of its op intervals clipped to the window;
  ``busy_s`` is the mean over devices;
* ``device_ops``: the executables (modules, as the trace names them) with
  the most device time, from the module line, where ops do not nest;
* ``idle_gaps``: device 0's idle time inside the window, summed by what the
  host was doing in each gap: the shortest host event that covers at least
  half of the gap, else the one that overlaps it most.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[str, float, float]        # (name, start_ns, end_ns)

WINDOW_SPAN = "bench.window"
TOP = 10


@dataclasses.dataclass
class Device:
    ops: List[Interval]             # its op line
    modules: List[Interval]         # the executables those ops ran in


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    host: List[Interval]            # host events, every thread


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def from_planes(planes) -> Trace:
    """A ``Trace`` from planes with ``name`` and ``lines``, each line with
    ``name`` and ``events`` of (name, start_ns, duration_ns): what
    ``ProfileData`` gives, or its JSON dump."""
    devices, host = [], []
    for name, lines in planes:
        lines = dict(lines)
        if DEVICE_PLANE.match(name):
            ops = lines.get("XLA Ops") or lines.get("XLA Modules")
            if ops is None:
                continue
            devices.append(Device(
                [(n, s, s + d) for n, s, d in ops],
                [(_module_name(n), s, s + d)
                 for n, s, d in lines.get("XLA Modules", ())]))
        elif name.startswith("/host:"):
            for evs in lines.values():
                host += [(n, s, s + d) for n, s, d in evs if d > 0]
    return Trace(devices, host)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return from_planes(
        (p.name, [(ln.name, [(e.name, e.start_ns, e.duration_ns)
                             for e in ln.events]) for ln in p.lines])
        for p in pd.planes)


def window_of(trace: Trace, span: str = WINDOW_SPAN) -> Tuple[float, float]:
    """The harness's own span around the traced window."""
    hits = [(s, t) for n, s, t in trace.host if n == span]
    if not hits:
        raise ValueError(f"no {span!r} span in the trace")
    return max(hits, key=lambda st: st[1] - st[0])


def union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return out


def _clip(evs: Sequence[Interval], lo: float, hi: float):
    return [(n, max(s, lo), min(t, hi)) for n, s, t in evs
            if t > lo and s < hi]


def _label(gap: Tuple[float, float], host: Sequence[Interval]) -> str:
    s, t = gap
    length = t - s
    best, over_best, covering = None, 0.0, []
    for n, hs, ht in host:
        if n == WINDOW_SPAN or ht <= s or hs >= t:
            continue
        over = min(t, ht) - max(s, hs)
        if over >= 0.5 * length:
            covering.append((ht - hs, n))
        if over > over_best:
            best, over_best = n, over
    if covering:
        return min(covering)[1]
    return best if best is not None else "no host span"


def reduce(trace: Trace, window: Tuple[float, float]) -> dict:
    lo, hi = window
    if not trace.devices:
        raise ValueError("the trace holds no device")
    busy, per_mod = [], defaultdict(float)
    first = None
    for dev in trace.devices:
        merged = union([(s, t) for _, s, t in _clip(dev.ops, lo, hi)])
        busy.append(sum(t - s for s, t in merged))
        for n, s, t in _clip(dev.modules or dev.ops, lo, hi):
            per_mod[n] += t - s
        if first is None:
            first = merged
    gaps, prev = [], lo
    for s, t in first:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, t)
    if hi > prev:
        gaps.append((prev, hi))
    host = _clip(trace.host, lo, hi)
    idle: Dict[str, float] = defaultdict(float)
    for g in gaps:
        idle[_label(g, host)] += g[1] - g[0]
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": sum(busy) / len(busy) * ns,
        "device_ops": [[n, v * ns] for n, v in
                       sorted(per_mod.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[n, v * ns] for n, v in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]],
        "n_gaps": len(gaps),
    }
