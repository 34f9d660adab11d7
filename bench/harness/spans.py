"""The program's own spans (``repro.obs``) in the traced window of a
pricing cell, for the per-layer metrics that split a request's time.

While a JAX profiler session captures, ``repro.obs`` records in this
process (the client side: ``frontend.candidates``, ``client.price`` with
``client.encode`` / ``client.wait`` / ``client.decode``, ``gc.collect``)
and mirrors each span as a ``repro.<name>`` host event whose metadata
carries its ``span_id`` and ``obs_t0_us``.  Each request carries its
context to the daemon, which returns that request's spans in the result
line (``serve.request`` and beneath it ``serve.decode``, ``serve.queue``,
``serve.price`` > ``engine.sweep`` > ``engine.save_cache`` >
``cache.compaction`` / ``durable.append``, ``serve.encode``,
``serve.send``, ``gc.collect``); the client ingests them beneath its
``client.price``.  The profiler is on only around the window, so the
records are the window's requests; set-up's sweep and the in-process
re-pricing after it are left out.

The mirrored pairs give the offset from the monotonic clock of the
records to the trace's clock; a record belongs to the window when its
start, so placed, lies inside ``bench.window``.  A pair whose two clock
readings around the annotation's start lie more than ``PAIR_US`` apart
(``obs_pair_us``: a collection or a thread switch fell between them)
cannot place the clocks that closely, so it is counted and left out of
the offset.  A program that records
no spans (a commit older than this) leaves every reader with nothing:
:func:`window` returns None and the metrics are left out.
"""
from __future__ import annotations

import statistics
from collections import defaultdict

from . import trace as tr
from .stats import percentile

MIRROR_PREFIX = "repro."
# widest pair, in us, that the offset is taken from: half the 0.1 ms to
# which the offsets are to agree
PAIR_US = 50.0


def window(run) -> dict | None:
    """The reduction of the window's spans (cached on the run), or None
    where the run was not traced or the program recorded nothing."""
    if "obs_window" not in run.record:
        run.record["obs_window"] = _window(run)
    return run.record["obs_window"]


def _window(run) -> dict | None:
    traced = run.record.get("trace")
    if traced is None:
        return None
    try:
        from repro import obs

        records = obs.spans()
    except Exception:
        return None
    if not records:
        return None
    starts = mirrored(tr.find_xplane(run.trace_dir()))
    return reduce(records, starts, traced["lo"], traced["hi"],
                  latency_s=run.record.get("latency_s"),
                  serve_s=run.record.get("serve_s"),
                  trace_s=run.record.get("trace_s"))


def mirrored(path: str) -> dict:
    """{span id: (start ns on the trace's clock, pair width us)} of every
    mirrored span."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(MIRROR_PREFIX):
                    stats = dict(e.stats)
                    sid = stats.get("span_id")
                    if sid is not None:
                        out[str(sid)] = (e.start_ns,
                                         float(stats.get("obs_pair_us", 0)))
    return out


def _by_name(records) -> dict:
    out: dict = defaultdict(float)
    for r in records:
        out[r.name] += r.dur_us / 1e3
    return out


def _mean(xs) -> float:
    return statistics.fmean(xs) if xs else 0.0


def reduce(records, starts: dict, lo: float, hi: float, *,
           latency_s=None, serve_s=None, trace_s=None) -> dict | None:
    """Split the window's requests into engine, persistence, wire and
    collector time.

    ``records`` are every ``SpanRecord`` of the run, ``starts`` the
    mirrored pairs (:func:`mirrored`), ``lo``/``hi`` the window on the
    trace's clock.  Requests are the window's ``client.price`` spans, in
    order; ``latency_s``, ``serve_s``, ``trace_s`` are the harness's own
    samples of the same window, for the notes.  Times in the result are
    ms per request."""
    loose = sorted((starts[r.span_id][1] for r in records
                    if r.span_id in starts
                    and starts[r.span_id][1] > PAIR_US), reverse=True)
    paired = [(starts[r.span_id][0] - r.t0_us * 1e3, r) for r in records
              if r.span_id in starts and starts[r.span_id][1] <= PAIR_US]
    if not paired:
        return None
    offsets = [off for off, _ in paired]
    offset = statistics.median(offsets)
    # pairs that disagree with the rest by more than 0.1 ms, worst first
    astray = sorted(((abs(off - offset), r) for off, r in paired
                     if abs(off - offset) > 1e5), key=lambda x: -x[0])

    def inside(r) -> bool:
        return lo <= r.t0_us * 1e3 + offset <= hi

    roots = sorted((r for r in records
                    if r.name == "client.price" and inside(r)),
                   key=lambda r: r.t0_us)
    n = len(roots)
    if not n:
        return None
    children = defaultdict(list)
    for r in records:
        if r.parent_id is not None:
            children[r.parent_id].append(r)

    def subtree(root) -> list:
        out, todo = [], [root]
        while todo:
            cur = todo.pop()
            for c in children.get(cur.span_id, ()):
                out.append(c)
                todo.append(c)
        return out

    sweeps: dict = {}           # span id -> (sweep, its save_cache spans)
    saves: dict = {}
    compactions: dict = {}
    appends: dict = {}
    rows = []
    for root in roots:
        below = subtree(root)
        own = [r for r in below if r.name == "engine.sweep"]
        for s in own:
            kids = [c for c in children.get(s.span_id, ())
                    if c.name == "engine.save_cache"]
            sweeps[s.span_id] = (s, kids)
        for r in below:
            if r.name == "engine.save_cache":
                saves[r.span_id] = r
            elif r.name == "cache.compaction":
                compactions[r.span_id] = r
            elif r.name == "durable.append":
                appends[r.span_id] = r
        rows.append({
            "ms": root.dur_us / 1e3,
            # the daemon rendering this request's spans, inside its wait
            "render_ms": root.args.get("spans_us", 0.0) / 1e3,
            "served": sum(1 for r in below if r.name == "serve.request"),
            "engine_ms": sum(s.dur_us for s in own) / 1e3,
            "persist_ms": sum(r.dur_us for r in below
                              if r.name == "engine.save_cache") / 1e3,
            "compaction": any(r.name == "cache.compaction" for r in below),
            "gc_ms": sum(r.dur_us for r in below
                         if r.name == "gc.collect") / 1e3,
            "parts": _by_name(r for r in below
                              if r.name.startswith(("client.", "serve."))),
        })
    engine_ms = sum(s.dur_us - sum(k.dur_us for k in kids)
                    for s, kids in sweeps.values()) / 1e3 / n
    persist_ms = sum(r.dur_us for r in saves.values()) / 1e3 / n
    wire_ms = sum(row["ms"] - row["engine_ms"] - row["render_ms"]
                  for row in rows) / n
    gcs = {(r.pid, r.span_id): r for r in records
           if r.name == "gc.collect" and inside(r)}
    gc_ms = sum(r.dur_us for r in gcs.values()) / 1e3 / n
    client_pid = roots[0].pid
    names = {r.span_id: r.name for r in records}
    where: dict = defaultdict(float)     # collector ms by the span it hit
    for r in gcs.values():
        side = "client" if r.pid == client_pid else "daemon"
        where[f"{side} in {names.get(r.parent_id, 'no span')}"] += (
            r.dur_us / 1e3)
    frontend = [r.dur_us / 1e3 for r in records
                if r.name == "frontend.candidates" and inside(r)]
    return {
        "n": n, "rows": rows, "offset_ns": offset,
        "offset_spread_ns": max(offsets) - min(offsets),
        "pairs": len(offsets), "loose_us": loose,
        "astray": [(r.name, dev * 1e-6, (r.t0_us * 1e3 + offset - lo) * 1e-9)
                   for dev, r in astray],
        "engine_ms": engine_ms, "persist_ms": persist_ms,
        "wire_ms": wire_ms, "gc_ms": gc_ms,
        "sweeps": len(sweeps),
        # in the order they ran: the store, and so each one, grows
        "compactions": [r.dur_us / 1e3 for r in sorted(
            compactions.values(), key=lambda r: r.t0_us)],
        "fsync_ms": sorted(r.args.get("fsync_us", 0.0) / 1e3
                           for r in appends.values()),
        "gc": sorted(((r.pid == client_pid, r.args.get("generation"),
                       r.dur_us / 1e3) for r in gcs.values()),
                     key=lambda g: (not g[0], str(g[1]), g[2])),
        "gc_where": dict(where),
        "frontend_ms": frontend,
        "latency_s": list(latency_s or ()),
        "serve_ms": _mean(serve_s) * 1e3 if serve_s else None,
        "trace_ms": _mean(trace_s) * 1e3 if trace_s else None,
    }


def tail(w: dict, q: float) -> list:
    """Rows of the requests whose harness latency is above its q-th
    percentile (by ``client.price`` time where the harness's samples do
    not pair one to one with the spans)."""
    lat = w["latency_s"]
    if len(lat) != w["n"]:
        lat = [row["ms"] for row in w["rows"]]
    cut = percentile(lat, q)
    return [row for row, x in zip(w["rows"], lat) if x > cut]


def tail_note(w: dict) -> str:
    """What the slow requests spend their time on, above p95 and p99."""
    parts = []
    for q in (95, 99):
        rows = tail(w, q)
        if not rows:
            continue
        parts.append(
            f"above p{q}: {len(rows)} requests, "
            f"{sum(r['compaction'] for r in rows)} with a compaction, "
            f"{sum(r['gc_ms'] > 1.0 for r in rows)} with over 1 ms of "
            f"collector; mean ms client.price "
            f"{_mean([r['ms'] for r in rows])!r}, engine.sweep "
            f"{_mean([r['engine_ms'] for r in rows])!r}, of it "
            f"engine.save_cache {_mean([r['persist_ms'] for r in rows])!r}"
            f", gc.collect {_mean([r['gc_ms'] for r in rows])!r}")
    return "; ".join(parts)
