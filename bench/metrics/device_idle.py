"""device_idle: share of the traced window in which no operation ran on
the device (1 - union of op intervals / window), averaged over chips."""
from bench.harness import trace as tr


def read(run):
    traced = run.record.get("trace")
    if traced is None:
        return None
    lo, hi = traced["lo"], traced["hi"]
    return 100.0 * (1.0 - tr.busy_ns(traced["trace"], lo, hi) / (hi - lo))
