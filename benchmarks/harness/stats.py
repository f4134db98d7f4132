"""The percentile and median arithmetic, in one place."""

from __future__ import annotations

import math
import statistics


def median(values) -> float:
    return float(statistics.median(values))


def percentile(values, q: float, missing: int = 0) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between
    closest ranks, over ``values`` plus ``missing`` samples that lie
    beyond any value (a request that failed or never answered). Where
    the percentile falls among the missing ones it is ``inf``."""
    data = sorted(float(v) for v in values)
    n = len(data) + int(missing)
    if n == 0:
        raise ValueError("percentile of no samples")
    pos = (n - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if hi >= len(data):
        return math.inf
    return data[lo] + (data[hi] - data[lo]) * (pos - lo)


def iqr_share(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, by ``statistics.quantiles(values, n=4)``: the spread that
    the bounds are set from."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(statistics.median(values))


def epoch_intervals(stamps) -> list:
    """Seconds between consecutive ``fit.epoch`` events of one ``fit``
    call: each holds one device epoch, one loss read, one write-back
    and one round of callbacks."""
    return [b - a for a, b in zip(stamps, stamps[1:])]


def window_rate(stamps, examples_per_epoch: int, chips: int):
    """All the window's examples over all its seconds, a chip: the
    window opens at the call's first ``fit.epoch`` event and closes at
    its last, and every epoch between them counts, slow ones too."""
    if len(stamps) < 2 or stamps[-1] <= stamps[0]:
        return None
    epochs = len(stamps) - 1
    return epochs * examples_per_epoch / (stamps[-1] - stamps[0]) / chips


def epoch_readings(stamps, examples_per_epoch: int, chips: int) -> list:
    """One reading of examples/s/chip for each interval of the window.
    Their median is a per-layer metric, the steady statistic beside the
    window's rate: one slow epoch moves the rate and not the median."""
    return [examples_per_epoch / g / chips
            for g in epoch_intervals(stamps) if g > 0]
