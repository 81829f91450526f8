"""Host seconds a step waits for its batch: the ``data`` phase of the step's
RoundRecord (window gather from the packed stream), median over the traced
steps. Layer: Cheetah runner (``cheetah/runner.py``). Moves
``tokens_per_s_per_chip``: the loop is synchronous, so the device idles for
all of it."""

import statistics


def read(run):
    waits = [float(r["phases"]["data"]) for r in run.records
             if "data" in (r.get("phases") or {})]
    return statistics.median(waits) if waits else None
