"""Traffic mode ``closed_cold``: one client asks for rankings of shapes the
run has not seen, one after another, each as soon as the last is answered.

The shapes are the grid the traffic file's ``axes`` span over the axes
the configuration's family draws (every other size stays as configured),
less the configuration's own shape, in an order drawn from the seed; no
shape comes twice, so neither the client's tracing cache nor the daemon's
memo can answer a request.  A request is timed from the start of tracing
to the decoded ranking.  Set-up prices the configuration's own shape and
compiles its pick; after the window that pick runs once on the chip and
is compared with the reference, so the cell still drives the device.

Every served ranking is checked against the candidates traced for it and
against the chip's peaks (``Run.ranking_faults``); ``library_sample`` of
them, drawn from the seed, are priced again in this process through
``repro.api.price`` and must match exactly.

Traffic parameters: ``axes`` ({axis: {"values": [...]}} or {axis: {"lo",
"hi", "step"}}), ``library_sample``.
"""
from __future__ import annotations

import gc
import itertools
import json
import random
import time

from .device import memory_peak_bytes
from .runner import COMPILES, max_abs_error, seed_key
from .stats import percentile


def axis_values(spec: dict) -> list:
    if "values" in spec:
        return list(spec["values"])
    return list(range(spec["lo"], spec["hi"] + 1, spec["step"]))


def shapes(program, shape: dict, axes: dict, seed: int) -> list:
    """Every shape of the grid but the configured one, in seeded order."""
    names = program.AXES
    own = tuple(program.axes(shape)[a] for a in names)
    grid = [v for v in itertools.product(*(axis_values(axes[a])
                                           for a in names)) if v != own]
    random.Random(seed).shuffle(grid)
    return [program.with_axes(shape, dict(zip(names, v))) for v in grid]


def run(r) -> None:
    import jax

    p, shape, traffic = r.program, r.shape, r.traffic
    if traffic["clients"] != 1:
        raise ValueError("closed_cold drives exactly one client")
    label = p.label(shape)
    cands = p.candidates(shape)
    r.phase("trace")
    ranking, skipped = r.price(cands, label)
    r.phase("price")
    r.log_ranking(label, ranking, skipped)
    faults = r.ranking_faults(shape, cands, ranking, skipped)
    args = p.inputs(shape, seed_key(r.seed))
    r.phase("inputs")
    if not ranking:
        raise RuntimeError(f"{label}: nothing feasible to run")
    pick = ranking[0][0]
    fn = r.compile(shape, pick, args)
    fn(*args).block_until_ready()
    r.phase("compile")
    pending = shapes(p, shape, traffic["axes"], r.seed)
    # set-up's objects move out of the collector's reach, so the window's
    # collections see only what the client makes per request, as in a
    # long-lived code generator
    gc.collect()
    gc.freeze()
    r.phase("warm-up")
    r.end_setup()

    latency, tracing, serving, served = [], [], [], []
    before = dict(COMPILES)
    r.start_trace()
    with jax.profiler.TraceAnnotation("bench.window"):
        start = time.perf_counter()
        deadline = start + r.seconds
        for s in pending:
            if time.perf_counter() >= deadline:
                break
            with jax.profiler.TraceAnnotation("bench.request"):
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.trace"):
                    c = p.candidates(s)
                t1 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.serve"):
                    try:
                        got = r.price(c, p.label(s))
                    except Exception as exc:     # counted, not fatal
                        got = exc
                t2 = time.perf_counter()
            latency.append(t2 - t0)
            tracing.append(t1 - t0)
            serving.append(t2 - t1)
            served.append((s, c, got))
        else:
            r.log(f"every one of {len(pending)} shapes was asked for "
                  f"before the window closed")
        window_s = time.perf_counter() - start
        with jax.profiler.TraceAnnotation("bench.pick_run"):
            out = fn(*args)
            out.block_until_ready()
    r.stop_trace()
    in_window = {k: COMPILES[k] - before[k] for k in COMPILES}
    r.record["memory_peak_bytes"] = memory_peak_bytes(r.devices)

    p95 = percentile(latency, 95)
    r.log(f"window: {window_s!r} s, {len(latency)} requests, "
          f"{sum(1 for x in latency if x > p95)} beyond the 95th "
          f"percentile; programs lowered in the window "
          f"{in_window['lowered']}, compiled {in_window['compiled']}")
    r.log("latency ms: " + ", ".join(
        f"p{q} {percentile(latency, q) * 1e3!r}" for q in (50, 90, 95, 99))
        + f", max {max(latency) * 1e3!r}")
    r.record.update(latency_s=latency, trace_s=tracing, serve_s=serving,
                    window_s=window_s, compiles_in_window=in_window)

    errors = 0
    for s, c, got in served:
        if isinstance(got, Exception):
            errors += 1
            r.log(f"request {p.label(s)} failed: {type(got).__name__}: "
                  f"{got}")
            continue
        bad = r.ranking_faults(s, c, *got)
        for k in faults:
            faults[k] += bad[k]
        if any(bad.values()):
            r.failed += 1
            r.log(f"ranking {p.label(s)} faulty: {bad}")
    for name, n in faults.items():
        r.check(f"ranking_{name}", n, 0)
    r.check("request_errors", errors, 0)
    r.failed += errors
    r.attempted = len(served)

    r.check("library_mismatch", _library_mismatches(r, served), 0)
    reference = r.ref.reference(shape, args)
    r.check(f"max_abs_error.{p.slug(pick)}", max_abs_error(out, reference),
            r.config["check"]["max_abs_error"])


def _library_mismatches(r, served: list) -> int:
    """Served rankings, of a seeded sample, that differ from the library's
    in-process answer to the same request, in order or in any time."""
    from repro.api import pallas_request, price

    answered = [x for x in served if not isinstance(x[2], Exception)]
    rng = random.Random(r.seed)
    sample = rng.sample(answered, min(r.traffic["library_sample"],
                                      len(answered)))
    machine = r.machine_name()
    bad = 0
    for s, c, (ranking, skipped) in sample:
        label = r.program.label(s)
        result = price(pallas_request(c, machine, workload=label))
        mine = [(json.dumps(e.config, sort_keys=True), e.estimate.total_time)
                for e in result.ranking(label, machine)]
        theirs = [(json.dumps(cfg, sort_keys=True), t) for cfg, t in ranking]
        bad += int(mine != theirs)
    return bad
