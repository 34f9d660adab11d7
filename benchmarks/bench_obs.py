"""Telemetry contract bench: overhead guard + trace coverage (DESIGN.md §14).

Observability must be free when off and honest when on.  This bench runs
the paper-grid pruned sweep (same workload as ``bench_pruned_search``)
twice — telemetry disabled, then enabled — and gates the contract:

  * **disabled overhead < 2%** — a disabled ``obs.span`` call is one
    module-global check returning a shared null object, plus, once jax is
    loaded, the JAX profiler's ``TraceMe.is_enabled`` check (recording
    follows the profiler); measured per call both before and after jax is
    imported, the larger scaled by the number of span sites the sweep
    actually crosses, the instrumentation tax on the cold sweep must stay
    under 2%;
  * **rankings bitwise identical** — telemetry may never perturb pricing:
    entries, limiters, and pruned sets match exactly across the two runs;
  * **coverage >= 90%** — the enabled run's ``engine.sweep`` span must
    cover at least 90% of the measured wall time (no untraced phases);
  * **worker spans merged** — pool workers ship their ``pool.chunk`` /
    ``engine.task.*`` spans back to the parent, parented under the main
    process's ``pool.run`` on the shared monotonic timeline;
  * **valid Chrome trace** — the export loads as trace-event JSON with
    unique span ids and per-process name metadata.

Per-phase wall-time shares (bounds/refine/rank, and the walk task's share
of structural work) ride in ``BENCH_obs.json``; ``scripts/check_bench.py``
gates the walk share as the per-phase time gate.
"""
import json
import os
import tempfile
import time

from repro import obs
from repro.api import gpu_request, price
from repro.core.engine import Explorer
from repro.core.machines import A100
from repro.core.selector import enumerate_gpu_configs
from repro.core.specs import star_stencil_3d

from .common import bench_json, emit, timed

TOP_K = 10
MICRO_CALLS = 200_000
WALL_SLACK = max(float(os.environ.get("BENCH_GATE_SLACK", "1.0")), 1.0)


def _rank(report):
    return [(e.config, e.estimate.perf_lups, e.limiter)
            for e in report.entries]


def _paper_sweep():
    # max_workers pinned (not defaulted) so the cross-process span-merge
    # contract is exercised even on single-core runners, identically in
    # the disabled and enabled runs
    spec = star_stencil_3d(r=4, domain=(48, 96, 128))
    configs = enumerate_gpu_configs(1024)
    return price(gpu_request(spec, A100, configs, top_k=TOP_K),
                 engine=Explorer(parallel=True, max_workers=2)).report


def _disabled_span_ns() -> float:
    """Per-call cost of a disabled span (the only cost instrumented code
    pays when telemetry is off)."""
    t0 = time.perf_counter()
    for _ in range(MICRO_CALLS):
        with obs.span("bench.noop", "bench", tag=1):
            pass
    return (time.perf_counter() - t0) / MICRO_CALLS * 1e9


def _disabled_span_ns_with_jax() -> float | None:
    """The same once jax is loaded: the flag check plus the profiler's
    capture check (None where jax is not installed)."""
    try:
        import jax  # noqa: F401 — loads the profiler's check
    except ImportError:
        return None
    assert not obs.profiling()
    return _disabled_span_ns()


def _trace_valid(trace: dict, records) -> bool:
    events = trace["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    ms = [e for e in events if e["ph"] == "M"]
    span_ids = [e["args"]["span_id"] for e in xs]
    ok = (
        trace.get("displayTimeUnit") == "ms"
        and all(e["ph"] in ("X", "M") for e in events)
        and len(xs) == len(records)
        and len(set(span_ids)) == len(span_ids)
        and all({"name", "cat", "ts", "dur", "pid", "tid", "args"}
                <= set(e) for e in xs)
        and {e["pid"] for e in ms} == {r.pid for r in records}
        and any(e["args"]["name"] == "repro" for e in ms)
    )
    # and it must survive a disk round trip (what Perfetto actually loads)
    with tempfile.TemporaryDirectory() as d:
        # read back by path: the atomic write replaces the file
        with open(obs.write_trace(os.path.join(d, "trace.json"),
                                  records)) as f:
            ok = ok and json.load(f) == trace
    return ok


def main():
    was_enabled = obs.enabled()     # run.py may be tracing the whole harness

    obs.disable()
    obs.reset()
    rep_off, t_off = timed(_paper_sweep)
    span_ns = _disabled_span_ns()
    span_ns_jax = _disabled_span_ns_with_jax()

    obs.enable()
    obs.reset()
    rep_on, t_on = timed(_paper_sweep)
    records = obs.spans()
    trace = obs.chrome_trace()
    obs.disable()
    obs.reset()
    if was_enabled:
        obs.enable()
        obs.ingest(records)     # keep our spans in the harness trace

    rankings_identical = (_rank(rep_on) == _rank(rep_off)
                          and [p.config for p in rep_on.pruned]
                          == [p.config for p in rep_off.pruned]
                          and rep_on.cache_stats == rep_off.cache_stats)

    # the instrumentation tax when disabled: every span site the sweep
    # crosses (counted from the enabled run) pays one null-span call
    worst_ns = max(span_ns, span_ns_jax or 0.0)
    overhead_frac = len(records) * worst_ns / (t_off * 1e3)
    overhead_ok = overhead_frac < 0.02 * WALL_SLACK

    main_pid = os.getpid()
    sweep = next(r for r in records if r.name == "engine.sweep")
    coverage = sweep.dur_us / t_on
    coverage_ok = coverage >= 0.9

    main_ids = {r.span_id for r in records if r.pid == main_pid}
    chunks = [r for r in records
              if r.name == "pool.chunk" and r.pid != main_pid]
    tasks = [r for r in records
             if r.cat == "task" and r.pid != main_pid]
    worker_spans_merged = (
        bool(chunks) and bool(tasks)
        and all(c.parent_id in main_ids for c in chunks))

    trace_valid = _trace_valid(trace, records)
    names = {r.name for r in records}
    phases_present = {"engine.sweep", "engine.bounds", "engine.refine",
                      "engine.rank", "pool.run", "pool.chunk"} <= names

    def _share(name):
        return sum(r.dur_us for r in records
                   if r.name == name) / sweep.dur_us

    task_wall = sum(r.dur_us for r in tasks) or 1.0
    walk_share = sum(r.dur_us for r in tasks
                     if r.name == "engine.task.walk") / task_wall
    shares = {"bounds": _share("engine.bounds"),
              "refine": _share("engine.refine"),
              "rank": _share("engine.rank")}

    emit(
        "obs/paper_grid_a100/disabled", t_off,
        f"span_ns={span_ns:.0f};span_ns_jax={span_ns_jax or 0:.0f};"
        f"overhead={overhead_frac:.4%};"
        f"overhead_ok={overhead_ok}",
    )
    emit(
        "obs/paper_grid_a100/enabled", t_on,
        f"spans={len(records)};pids={len({r.pid for r in records})};"
        f"coverage={coverage:.3f};identical={rankings_identical};"
        f"merged={worker_spans_merged};walk_share={walk_share:.3f}",
    )

    assert rankings_identical, \
        "telemetry must never perturb pricing (rankings diverged)"
    assert overhead_ok, (
        f"disabled telemetry overhead {overhead_frac:.2%} >= 2% "
        f"({worst_ns:.0f} ns/span x {len(records)} sites)")
    assert coverage_ok, f"span tree covers only {coverage:.1%} of wall time"
    assert worker_spans_merged, "worker spans missing or unparented"
    assert trace_valid, "Chrome trace export failed validation"

    bench_json("obs", {
        "n_spans": len(records),
        "n_pids": len({r.pid for r in records}),
        "disabled_s": t_off / 1e6,
        "enabled_s": t_on / 1e6,
        "disabled_span_ns": span_ns,
        "disabled_span_ns_jax": span_ns_jax,
        "overhead_frac": overhead_frac,
        "overhead_ok": overhead_ok,
        "coverage": coverage,
        "coverage_ok": coverage_ok,
        "rankings_identical": rankings_identical,
        "worker_spans_merged": worker_spans_merged,
        "trace_valid": trace_valid,
        "phases_present": phases_present,
        "walk_share": walk_share,
        "phase_shares": shares,
    })


if __name__ == "__main__":
    main()
