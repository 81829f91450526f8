"""The busiest held expert's load over the mean held expert's: the program's
``moe_max_expert_load`` counter (the largest number of assignments any held
expert got in any expert layer of the step) over ``moe_assignments_held /
(expert layers x experts held)``, mean over the traced steps. 1 is a balanced
router; the selection bias moves it down step by step. Layer: expert layer
(``parallel/moe.py``). Moves ``tokens_per_s_per_chip``: the grouped product's
tiles follow the groups, so skew costs little until an expert starves."""

import statistics


def read(run):
    ratios = []
    for r in run.records:
        c = r.get("counters") or {}
        if c.get("moe_assignments_held") and "moe_max_expert_load" in c:
            mean = c["moe_assignments_held"] / (
                run.facts["expert_layers"] * run.facts["experts_held"])
            ratios.append(c["moe_max_expert_load"] / mean)
    return statistics.fmean(ratios) if ratios else None
