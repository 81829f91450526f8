"""Share of a step's routing assignments that reached an expert this chip
holds, in percent: the program's ``moe_assignments_held`` counter (RoundRecord
``counters``, summed over the expert layers) over ``experts per token x tokens
x expert layers``. 12.5% under a balanced router with 8 of 64 experts held;
the grouped products' work follows it. Layer: expert layer
(``parallel/moe.py``). Moves ``tokens_per_s_per_chip``."""

import statistics


def read(run):
    held = [r["counters"]["moe_assignments_held"] for r in run.records
            if "moe_assignments_held" in (r.get("counters") or {})]
    if not held or not run.facts.get("assignments_per_step"):
        return None
    return 100.0 * statistics.fmean(held) / run.facts["assignments_per_step"]
