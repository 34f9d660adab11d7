"""Traffic mode ``sweep``: time every candidate of the cell's own shape.

Set-up traces the generator's candidates, has the daemon price them,
compiles every candidate, and warms each up, timing it to size its
visits.  A candidate that does not compile, or compiles to anything but a
Mosaic kernel, is refused: it is left out of the window, counted as
attempted and failed, and the check ``refused`` (limit 0) makes the run
not correct, so no candidate can leave the set that ``regret`` and
``rank_tau`` are taken over unseen.  The window then
visits the candidates round-robin, in the served ranking's order: each
visit makes back-to-back calls, at most ``in_flight`` of them queued, and
ends in ``block_until_ready``; every round is whole but the last, which
stops at the end of the window.  The profiler records the window in every
run: each candidate's time per call is the device time of its program's
runs in the trace (``jit_bench_<candidate>``, the kernel and the XLA ops
around it) over those runs.  The host clock's time per call over all its
visits is reported beside it on an earlier line: a TPU v5 lite host was
seen to stall for 0.06-1.2 s now and then, longer than any queue of calls
that fits beside the kept outputs can cover.  After the
window every candidate's last output is compared with the reference.
``memory_peak_bytes`` is read at the end of set-up, whose warm-up runs
every candidate's visits as the window does: the window adds only the
last outputs the check keeps (one per candidate), which no deployment
holds, and the peak after the window is printed on an earlier line.

Traffic parameters: ``visit_seconds`` (the length a visit is sized to),
``in_flight`` (calls queued on the device at once).
"""
from __future__ import annotations

import gc
import json
import math
import time

from . import trace as tr
from .device import memory_peak_bytes
from .runner import COMPILES, max_abs_error, seed_key


def _visit(fn, args, calls: int, in_flight: int):
    """``calls`` back-to-back calls with at most ``in_flight`` queued; the
    last output, ready."""
    queued = []
    for _ in range(calls):
        queued.append(fn(*args))
        if len(queued) > in_flight:
            queued.pop(0).block_until_ready()
    out = queued[-1]
    out.block_until_ready()
    return out


def _size_visits(compiled: dict, args, traffic: dict) -> dict:
    """Calls per visit of each candidate, sized to ``visit_seconds`` from
    the faster of two timed visits of three calls after a warm-up call."""
    calls = {}
    for key, (_, fn) in compiled.items():
        fn(*args).block_until_ready()
        per_call = math.inf
        for _ in range(2):
            t = time.perf_counter()
            _visit(fn, args, 3, traffic["in_flight"])
            per_call = min(per_call, (time.perf_counter() - t) / 3)
        calls[key] = max(1, round(traffic["visit_seconds"] / per_call))
    return calls


def _device_per_call(traced: dict, program: str, calls: int) -> float:
    """Device seconds per run of ``program`` in the window's trace (a
    trace that lost runs is reported, and averaged over what it holds)."""
    ns, runs = tr.program_ns(traced["trace"], program)
    if not runs:
        raise RuntimeError(f"{program}: no run in the trace of the window")
    if runs != calls:
        print(f"# trace holds {runs} runs of {program}, {calls} calls made",
              flush=True)
    return ns * 1e-9 / runs


def _window(r, compiled: dict, args, calls: dict, traffic: dict) -> tuple:
    """Visit the candidates round-robin for ``r.seconds``: (calls and
    seconds per candidate, each one's last output, rounds, window seconds,
    programs lowered and compiled inside the window)."""
    import jax

    stats = {key: [0, 0.0] for key in compiled}
    outs = {}
    before = dict(COMPILES)
    r.start_trace(always=True)
    with jax.profiler.TraceAnnotation("bench.window"):
        start = time.perf_counter()
        deadline = start + r.seconds
        rounds = 0
        while rounds == 0 or time.perf_counter() < deadline:
            for key, (_, fn) in compiled.items():
                if rounds and time.perf_counter() >= deadline:
                    break
                with jax.profiler.TraceAnnotation(f"bench.visit.{key}"):
                    t = time.perf_counter()
                    outs[key] = _visit(fn, args, calls[key],
                                       traffic["in_flight"])
                    stats[key][1] += time.perf_counter() - t
                stats[key][0] += calls[key]
            rounds += 1
        window_s = time.perf_counter() - start
    r.stop_trace(always=True)
    in_window = {k: COMPILES[k] - before[k] for k in COMPILES}
    return stats, outs, rounds, window_s, in_window


def run(r) -> None:
    p, shape, traffic = r.program, r.shape, r.traffic
    label = p.label(shape)
    cands = p.candidates(shape)
    r.phase("trace")
    ranking, skipped = r.price(cands, label)
    r.phase("price")
    r.log_ranking(label, ranking, skipped)
    faults = r.ranking_faults(shape, cands, ranking, skipped)
    for name, n in faults.items():
        r.check(f"ranking_{name}", n, 0)
    predicted = {json.dumps(c, sort_keys=True): t for c, t in ranking}

    args = p.inputs(shape, seed_key(r.seed))
    r.phase("inputs")
    # served order first, then what the estimator skipped
    order = [c for c, _ in ranking] + list(skipped)
    compiled, refused = {}, []
    for cfg in order:
        try:
            fn = r.compile(shape, cfg, args)
        except Exception as exc:         # refused: not timed, not correct
            refused.append(p.slug(cfg))
            r.log(f"refused {p.slug(cfg)}: "
                  f"{type(exc).__name__}: {str(exc)[:300]!r}")
            continue
        compiled[p.slug(cfg)] = (cfg, fn)
    r.check("refused", len(refused), 0)
    if not ranking or p.slug(ranking[0][0]) not in compiled:
        raise RuntimeError(f"{label}: the served pick did not compile")
    r.phase("compile")

    # the window allocates next to nothing: with the collector off, no
    # collection of set-up's garbage stalls the host inside a visit
    gc.collect()
    gc.disable()
    try:
        calls = _size_visits(compiled, args, traffic)
        r.phase("warm-up")
        r.end_setup()
        r.record["memory_peak_bytes"] = memory_peak_bytes(r.devices)
        stats, outs, rounds, window_s, in_window = _window(r, compiled, args,
                                                           calls, traffic)
    finally:
        gc.enable()
    r.log(f"memory peak: {r.record['memory_peak_bytes']!r} bytes in set-up, "
          f"{memory_peak_bytes(r.devices)!r} with the window's kept outputs")

    pick = p.slug(ranking[0][0])
    traced = r.record["trace"]
    cand = {}
    for key, (cfg, _) in compiled.items():
        n, secs = stats[key]
        program = f"jit_bench_{key}"
        # a control run's programs are one and the same, which XLA's cache
        # runs under the first one's name: only its checks count, so its
        # times are the host clock's
        cand[key] = {"program": program, "calls": n,
                     "per_call_s": (secs / n if r.control else
                                    _device_per_call(traced, program, n)),
                     "host_per_call_s": secs / n,
                     "predicted_s": predicted.get(json.dumps(cfg,
                                                             sort_keys=True))}
        r.log(f"candidate {key}: calls={n} device_per_call_ms="
              f"{cand[key]['per_call_s'] * 1e3!r} host_per_call_ms="
              f"{secs / n * 1e3!r} predicted_ms="
              f"{(cand[key]['predicted_s'] or float('nan')) * 1e3!r}")
    r.log(f"window: {window_s!r} s, {rounds} rounds, "
          f"{len(compiled)} candidates timed, {len(refused)} refused "
          f"{refused}; programs lowered in the window "
          f"{in_window['lowered']}, compiled {in_window['compiled']}")
    r.record.update(pick=pick, candidates=cand, window_s=window_s,
                    work=r.work.work(shape), compiles_in_window=in_window)

    del compiled
    reference = r.ref.reference(shape, args)
    limit = r.config["check"]["max_abs_error"]
    r.attempted = len(outs) + len(refused)
    r.failed = len(refused)
    for key in list(outs):
        err = max_abs_error(outs.pop(key), reference)
        if not r.check(f"max_abs_error.{key}", err, limit):
            r.failed += 1
