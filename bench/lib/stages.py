"""Deltas of the monitor's per-frame stage spans over a window.

The program records ``repro_frame_stage_us{stage=ad|reduce|ps|prov|write|
publish}`` as integer-microsecond histograms; a snapshot series is
``counts[32] + [sum, count]``.
"""
from __future__ import annotations

import json
from typing import Dict, Tuple

FAMILY = "repro_frame_stage_us"


def read(snapshot: dict) -> Dict[str, Tuple[int, int]]:
    """{stage: (sum_us, count)} from a telemetry registry snapshot."""
    fam = snapshot.get(FAMILY)
    if not fam:
        return {}
    out = {}
    for key, vec in fam["series"].items():
        labels = dict(json.loads(key))
        out[labels["stage"]] = (int(vec[-2]), int(vec[-1]))
    return out


def delta(before: dict, after: dict) -> Dict[str, Tuple[int, int]]:
    a, b = read(before), read(after)
    return {
        stage: (s - a.get(stage, (0, 0))[0], n - a.get(stage, (0, 0))[1])
        for stage, (s, n) in b.items()
    }
