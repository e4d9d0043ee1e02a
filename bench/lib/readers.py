"""Arithmetic the per-layer metric readers share."""

from __future__ import annotations

import numpy as np


def idle_pct(run):
    """Share of the traced window in which no operation ran on the device,
    mean over the cell's chips."""
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return float(np.mean([100.0 * (1.0 - b / t.window_s) for b in t.busy_s]))
