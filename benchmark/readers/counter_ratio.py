"""Ratio of sums of counters over the measured window, in percent.

Parameters: ``numerator`` and ``denominator`` (lists of counter names),
and optionally ``denominator_times`` naming a number of the context (such
as ``top_rung``) that the denominator is multiplied by. Counters are the
program's ``Metrics`` counters, as their difference over the window.
Nothing to divide by: nothing is returned.
"""


def read(params, ctx):
    counters = ctx["counters"]
    num = sum(counters.get(name, 0.0) for name in params["numerator"])
    den = sum(counters.get(name, 0.0) for name in params["denominator"])
    if "denominator_times" in params:
        den *= float(ctx[params["denominator_times"]])
    if den <= 0:
        return None
    return 100.0 * num / den
