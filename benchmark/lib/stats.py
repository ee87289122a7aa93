"""Percentile, window and spread arithmetic — the yardstick's own, so a
later PR cannot change how a tail or a spread is taken."""

import math
import statistics
from typing import Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0–100) by linear interpolation between
    order statistics (numpy's default method). None for no values."""
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)


def in_window(stamps: Iterable[float], t_open: float,
              t_close: float) -> List[float]:
    """The stamps with ``t_open <= t < t_close``."""
    return [t for t in stamps if t_open <= t < t_close]


def gaps_in_window(token_stamps: Sequence[float], t_open: float,
                   t_close: float) -> List[float]:
    """Gaps between successive tokens of ONE request whose later token was
    stamped inside the window (a gap belongs to the moment the client
    stopped waiting for it)."""
    return [b - a for a, b in zip(token_stamps, token_stamps[1:])
            if t_open <= b < t_close]


def iqr_share(values: Sequence[float]) -> float:
    """Spread as the contract takes it: the distance between the first and
    third quartile (``statistics.quantiles(values, n=4)``) as a share of
    the median."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
