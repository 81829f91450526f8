"""Real samples over ``cohort x capacity``, in percent: the share of the
round's batch grid that is not masked padding. Layer: Parrot engine (it packs
every client to the largest one's capacity). Source: the ``examples`` counter
of the traced rounds' RoundRecords (the summed true counts of the sampled
cohort). Moves ``rounds_per_s``: the device does the whole grid."""


def read(run):
    examples = [r["examples"] for r in run.records if r.get("examples")]
    if not examples:
        return None
    grid = run.facts["cohort"] * run.facts["cap"]
    return 100.0 * sum(examples) / (len(examples) * grid)
