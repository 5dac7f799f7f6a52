"""Arithmetic shared by the metric readers."""
from __future__ import annotations

import numpy as np


def percentile(values, p: float) -> float:
    """Linear-interpolated percentile (the program's ``core/metrics.py``
    arithmetic, copied so that the yardstick stays put)."""
    return float(np.percentile(np.asarray(values, dtype=float), p))


def meets_slo(ttft: float, gaps, ttft_slo: float, tbt_slo: float,
              tbt_pct: float = 99.0) -> bool:
    """Did one finished request meet both limits: its TTFT, and the
    ``tbt_pct`` percentile of its own token gaps (the program's
    ``core/metrics.py`` rule, on host-clock stamps)."""
    if ttft > ttft_slo:
        return False
    return not len(gaps) or percentile(gaps, tbt_pct) <= tbt_slo
