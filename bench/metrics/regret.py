"""regret: the pick's device time per call over the lowest device time per
call of any candidate timed in the same window."""


def read(run):
    cand = run.record.get("candidates")
    if not cand:
        return None
    best = min(c["per_call_s"] for c in cand.values())
    return cand[run.record["pick"]]["per_call_s"] / best
