"""Reduce a JAX profiler trace to device busy time, per-op and per-program
device time, and idle gaps labelled by what the host was doing.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote, with
``jax.profiler.ProfileData`` and nothing else, into plain tuples; every
other function here is pure and is checked on a small recorded trace
(``bench/tests/data/trace_small.json``).

Shape of a loaded trace::

    {"devices": {0: {"ops": [(name, start_ns, dur_ns), ...],
                     "modules": [(name, start_ns, dur_ns), ...]}},
     "host": [(thread, name, start_ns, dur_ns), ...]}

``ops`` are the events of a device plane's "XLA Ops" line (one per HLO op
that ran), ``modules`` those of its "XLA Modules" line (one per program
execution, named after the jitted function).
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_LINES = {"XLA Ops": "ops", "XLA Modules": "modules"}
NAME_CHARS = 160  # an op's name is its HLO text; the head names op, shape and dtype


def load(log_dir: str) -> Optional[dict]:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        return None
    data = ProfileData.from_file(paths[-1])
    out: dict = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = _LINES.get(line.name)
                if key:
                    dev[key].extend((e.name, e.start_ns, e.duration_ns) for e in line.events)
            out["devices"][int(m.group(1))] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    (line.name, e.name, e.start_ns, e.duration_ns) for e in line.events
                )
    return out


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _intervals(events) -> List[Tuple[float, float]]:
    return [(s, s + d) for _name, s, d in events]


def busy_intervals(dev: dict) -> List[Tuple[float, float]]:
    """Union of the device's op intervals (its programs', where no op line)."""
    return union(_intervals(dev["ops"] or dev["modules"]))


def busy_s(dev: dict) -> float:
    return sum(e - s for s, e in busy_intervals(dev)) / 1e9


def leaf_ops(ops) -> list:
    """The ops that enclose no other op: a ``while`` or ``conditional`` op's
    event spans the ops of its body, which are events of their own."""
    ops = sorted(ops, key=lambda e: (e[1], -e[2]))
    return [e for e, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] >= e[1] + e[2]]


def op_seconds(dev: dict) -> Dict[str, float]:
    """Device seconds per op name, summed over the trace's leaf ops."""
    tot: Dict[str, float] = defaultdict(float)
    for name, _s, d in leaf_ops(dev["ops"]):
        tot[name] += d / 1e9
    return dict(tot)


def module_stats(dev: dict, substring: str) -> Tuple[int, float]:
    """(executions, device seconds) of the programs whose name holds ``substring``."""
    hits = [d for name, _s, d in dev["modules"] if substring in name]
    return len(hits), sum(hits) / 1e9


def idle_gaps(busy: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Gaps between consecutive busy intervals (leading/trailing idle excluded)."""
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]


def label_gaps(gaps, host) -> List[str]:
    """For each gap, the name of the host event that overlaps it most; ties
    go to the shorter (more specific) event.  "host idle" where none does."""
    import numpy as np

    if not host:
        return ["host idle"] * len(gaps)
    starts = np.fromiter((h[2] for h in host), np.float64, len(host))
    durs = np.fromiter((h[3] for h in host), np.float64, len(host))
    labels = []
    for s, e in gaps:
        ov = np.minimum(e, starts + durs) - np.maximum(s, starts)
        if not (ov > 0).any():
            labels.append("host idle")
            continue
        best = np.lexsort((durs, -ov))[0]  # most overlap, then shortest
        labels.append(host[int(best)][1])
    return labels


def breakdown(trace: dict, top: int = 10) -> dict:
    """Top device ops by time and the ``top`` longest idle gaps, each
    labelled by host activity, over every device in the trace."""
    ops: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    for dev in trace["devices"].values():
        for name, sec in op_seconds(dev).items():
            ops[name] += sec
        gaps.extend(idle_gaps(busy_intervals(dev)))
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    labels = label_gaps(top_gaps, trace["host"])
    return {
        "device_ops": [[name[:NAME_CHARS], sec] for name, sec in top_ops],
        "idle_gaps": [[lab, (g[1] - g[0]) / 1e9] for lab, g in zip(labels, top_gaps)],
    }


def mean_busy_s(trace: dict) -> float:
    """Device busy seconds averaged over the devices in the trace."""
    devs = list(trace["devices"].values())
    return sum(busy_s(d) for d in devs) / len(devs) if devs else 0.0
