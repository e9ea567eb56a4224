"""Percentile arithmetic, in one place."""


def percentile(values, q: float):
    """``q`` in [0, 100]; linear interpolation between order statistics
    (numpy's default).  None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))
