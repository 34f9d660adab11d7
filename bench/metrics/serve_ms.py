"""serve_ms: mean ``PriceClient.price`` round trip per request: encoding,
the socket, the daemon's scheduler and engine sweep, decoding."""
import statistics


def read(run):
    t = run.record.get("serve_s")
    return statistics.fmean(t) * 1e3 if t else None
