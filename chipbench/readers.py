"""Shared arithmetic of the per-layer readers (``metrics/<name>.py``).
Each returns None where the run holds nothing to read."""
from __future__ import annotations

from typing import Optional


def hist_mean(run, name: str, scale: float = 1.0) -> Optional[float]:
    """Mean of a program histogram's observations in the window."""
    count, total = run.obs.get(name, (0, 0.0))
    return total / count * scale if count else None


def idle_share(run) -> Optional[float]:
    """100 x the share of the traced window in which the device ran no
    operation (busy time averaged over the chips)."""
    t = run.trace
    if t is None or t.busy_s is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
