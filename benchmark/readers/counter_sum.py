"""Sum of counters over the measured window (a count).

Parameters: ``counters``. Counters named ``bench_*`` are the harness's
own (``bench_backend_compiles``: compilations JAX reported inside the
window). A counter that never moved counts 0: a count of 0 is a reading.
"""


def read(params, ctx):
    return float(sum(ctx["counters"].get(name, 0.0)
                     for name in params["counters"]))
