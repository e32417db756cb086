"""Quotient of two sums of counters over the measured window, times a
scale: ``scale`` x sum(``numerator``) / sum(``denominator``), such as
1000 x seconds busy / frames = milliseconds a frame.

Parameters: ``numerator`` and ``denominator`` (lists of counter names) and
``scale``. Counters are the program's ``Metrics`` counters as their
difference over the window; one that did not move is absent from it.
Nothing is returned when the denominator is 0, or when no counter of the
numerator moved: a program without that counter gives no reading, not 0.
"""


def quotient(numerator, denominator, scale):
    """``numerator`` and ``denominator`` are lists of window deltas, None
    for a counter that did not move."""
    moved = [v for v in numerator if v is not None]
    den = sum(v for v in denominator if v is not None)
    if not moved or den <= 0:
        return None
    return float(scale) * sum(moved) / den


def read(params, ctx):
    counters = ctx["counters"]
    return quotient([counters.get(name) for name in params["numerator"]],
                    [counters.get(name) for name in params["denominator"]],
                    params["scale"])
