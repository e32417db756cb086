"""Percentile of the durations of one stage's spans, in milliseconds.

Parameters: ``stage`` (the ``Tracer`` span's stage), ``percentile``, and
optionally ``where`` ({attribute: value}) to keep only some spans. Spans
are those of the batch topic that ended inside the window. No span of the
stage: nothing is returned.
"""



def read(params, ctx):
    where = params.get("where", {})
    durs = [s["dur"] for s in ctx["spans"]
            if s.get("stage") == params["stage"]
            and all(s.get(k) == v for k, v in where.items())]
    if not durs:
        return None
    durs.sort()
    q = float(params["percentile"]) / 100.0
    if len(durs) == 1:
        return durs[0] * 1e3
    pos = q * (len(durs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(durs) - 1)
    return (durs[lo] + (durs[hi] - durs[lo]) * (pos - lo)) * 1e3


