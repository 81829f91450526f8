"""Host seconds a round spends before the device can start it: the
``sample``, ``gather``, ``prep`` and ``dispatch`` phases of the round's
RoundRecord, median over the traced rounds. Layer: Parrot engine
(``simulation/sp_api.py``, ``round_engine.py``). Moves ``rounds_per_s`` once
it is no longer hidden behind the previous round's device time."""

import statistics

PHASES = ("sample", "gather", "prep", "dispatch")


def read(run):
    per_round = [sum(float(r["phases"].get(p, 0.0)) for p in PHASES)
                 for r in run.records if r.get("phases")]
    return statistics.median(per_round) if per_round else None
