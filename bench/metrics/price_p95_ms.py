"""price_p95_ms: 95th percentile, over every request of the window, of the
client-side time from the start of tracing to the decoded ranking."""
from bench.harness.stats import percentile


def read(run):
    lat = run.record.get("latency_s")
    if not lat:
        return None
    return percentile(lat, 95) * 1e3
