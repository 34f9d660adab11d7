"""rank_tau: Kendall's tau-b between the served ranking's predicted times
and the window's device times per call, over every timed candidate the
estimator priced."""
from bench.harness.stats import kendall_tau_b


def read(run):
    cand = run.record.get("candidates")
    if not cand:
        return None
    pairs = [(c["predicted_s"], c["per_call_s"]) for c in cand.values()
             if c["predicted_s"] is not None]
    if len(pairs) < 2:
        return None
    return kendall_tau_b([p for p, _ in pairs], [m for _, m in pairs])
