"""Spread arithmetic, kept with the benchmark (tools/spread.py uses it)."""

import statistics
from typing import Sequence


def iqr_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the median,
    with the quartiles of ``statistics.quantiles(values, n=4)``."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
