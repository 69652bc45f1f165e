"""Percentiles by nearest rank over all samples."""

from __future__ import annotations

import math


def percentile(values, share: float) -> "float | None":
    if not values:
        return None
    ordered = sorted(values)
    return ordered[max(math.ceil(share * len(ordered)) - 1, 0)]
