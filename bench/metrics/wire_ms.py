"""wire_ms: what a request spends outside the engine sweep: over the
traced window's requests, the sum of each ``client.price`` span less the
``engine.sweep`` spans beneath it and the daemon's rendering of the
request's spans (``spans_us``), over their number: codec, socket, queue
and thread hand-offs on both sides (``repro.obs`` spans; see
``bench/harness/spans.py``).  The note checks the split against the
harness's own clocks and the clock offset of the mirrored spans."""
from bench.harness import spans

PARTS = ("client.encode", "client.wait", "client.decode", "serve.request",
         "serve.decode", "serve.queue", "serve.price", "serve.encode",
         "serve.send")


def read(run):
    w = spans.window(run)
    if w is None:
        return None
    n, rows = w["n"], w["rows"]
    served = [row["served"] for row in rows]
    split = w["engine_ms"] + w["persist_ms"] + w["wire_ms"]
    render = sum(row["render_ms"] for row in rows) / n
    note = (f"{n} client.price spans, {len(w['latency_s'])} requests timed "
            f"by the harness; serve.request per request {min(served)}-"
            f"{max(served)}; engine + persist + wire {split!r} ms")
    if w["serve_ms"]:
        note += (f" against serve_ms {w['serve_ms']!r} "
                 f"({100.0 * (split / w['serve_ms'] - 1.0)!r}%)")
    note += f"; the daemon rendering the spans {render!r} ms per request"
    front = w["frontend_ms"]
    if front:
        mean = sum(front) / n
        note += f"; frontend.candidates {mean!r} ms per request"
        if w["trace_ms"]:
            note += (f" against trace_ms {w['trace_ms']!r} "
                     f"({100.0 * (mean / w['trace_ms'] - 1.0)!r}%)")
    astray, loose = w["astray"], w["loose_us"]
    note += (f"; clock offset spread {w['offset_spread_ns'] * 1e-6!r} ms "
             f"over {w['pairs']} mirrored spans, {len(astray)} more than "
             f"0.1 ms from their median"
             + "".join(f", {name} {dev!r} ms off at {at!r} s"
                       for name, dev, at in astray[:5])
             + f"; {len(loose)} pairs wider than {spans.PAIR_US!r} us "
             f"left out"
             + (f", the widest {loose[0]!r} us" if loose else "")
             + "; per request ms: "
             + ", ".join(f"{p} {sum(r['parts'].get(p, 0.0) for r in rows) / n!r}"
                         for p in PARTS))
    return w["wire_ms"], note
