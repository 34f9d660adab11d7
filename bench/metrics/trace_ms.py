"""trace_ms: mean time per request spent tracing the generator's
candidates (``candidate_specs``, client side)."""
import statistics


def read(run):
    t = run.record.get("trace_s")
    return statistics.fmean(t) * 1e3 if t else None
