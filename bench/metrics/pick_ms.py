"""pick_ms: device time per call of the configuration the served ranking
put first: its program's runs in the profiler trace of the window,
summed, over their number."""


def read(run):
    cand = run.record.get("candidates")
    if not cand:
        return None
    return cand[run.record["pick"]]["per_call_s"] * 1e3
