"""engine_ms: the daemon's engine sweep per request, less its
persistence: over the traced window's requests, the sum of each
``engine.sweep`` span's time less its ``engine.save_cache`` children, over
the number of ``client.price`` spans (``repro.obs`` spans; see
``bench/harness/spans.py``)."""
from bench.harness import spans


def read(run):
    w = spans.window(run)
    if w is None:
        return None
    per = [row["engine_ms"] for row in w["rows"]]
    return w["engine_ms"], (
        f"{w['sweeps']} engine.sweep spans in {w['n']} requests; "
        f"engine.sweep with persistence: max {max(per)!r} ms")
