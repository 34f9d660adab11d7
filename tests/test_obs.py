"""Unified telemetry (DESIGN.md §14): span collection, the metrics
registry, exporters, and the end-to-end guarantees — disabled no-op,
cross-process span merging, and telemetry-invariant rankings.
"""
import json
import os
import subprocess
import sys
import threading

import pytest

from repro import obs
from repro.obs import metrics as obs_metrics
from repro.obs.metrics import (
    CACHE_STATS_KEYS,
    CounterGroup,
    MetricSpec,
    cache_stats_view,
)

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")


@pytest.fixture(autouse=True)
def _clean_obs():
    """Telemetry state is process-global: every test starts and ends
    disabled and empty so ordering never leaks spans between tests."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


# ========================================================================
# spans
# ========================================================================
def test_disabled_span_is_shared_noop_singleton():
    s1 = obs.span("engine.sweep", kind="pruned")
    s2 = obs.span("pool.chunk")
    assert s1 is s2                       # no allocation on the off path
    assert s1.enabled is False
    with s1 as sp:
        sp.add(cells=3)                   # no-op, no error
    assert obs.spans() == []


def test_enabled_spans_record_nesting_and_timing():
    obs.enable()
    with obs.span("outer", kind="pruned") as sp:
        with obs.span("inner", "task"):
            pass
        sp.add(cells=2)
    recs = obs.spans()
    assert [r.name for r in recs] == ["inner", "outer"]  # exit order
    inner, outer = recs
    assert outer.parent_id is None
    assert inner.parent_id == outer.span_id
    assert inner.trace_id == outer.trace_id
    assert outer.args == {"kind": "pruned", "cells": 2}
    assert inner.cat == "task" and outer.cat == "phase"
    assert outer.dur_us >= inner.dur_us >= 0.0
    assert inner.t0_us >= outer.t0_us
    assert outer.pid == os.getpid()


def test_span_records_error_class_on_exception():
    obs.enable()
    with pytest.raises(ValueError):
        with obs.span("boom"):
            raise ValueError("x")
    (rec,) = obs.spans()
    assert rec.args["error"] == "ValueError"


def test_spans_are_thread_safe_and_threads_nest_independently():
    obs.enable()
    n_threads, per_thread = 8, 25

    def work(i):
        for j in range(per_thread):
            with obs.span(f"t{i}", "thread"):
                with obs.span(f"t{i}.child", "thread"):
                    pass

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = obs.spans()
    assert len(recs) == n_threads * per_thread * 2
    assert len({r.span_id for r in recs}) == len(recs)   # unique ids
    by_id = {r.span_id: r for r in recs}
    for r in recs:
        if r.parent_id is not None:
            # children parent into their own thread's span, never across
            assert by_id[r.parent_id].tid == r.tid


def test_adopt_drain_ingest_round_trip():
    """The worker side of the pool boundary, in-process: a chunk run under
    the parent's context ships its spans, parented there, and the parent
    ingests them."""
    from repro.core.engine.pool import _pool_batch

    obs.enable()
    with obs.span("parent") as sp:
        ctx = obs.current_context()
        assert ctx == (sp.trace_id, sp.span_id)
    parent_rec = obs.spans()[0]

    def task(x):
        with obs.span("task", "task"):
            return x + 1

    shipped = []

    def worker():
        shipped.append(_pool_batch([(task, (1,)), (task, (2,))], ctx))

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    (tag, outcomes, records), = shipped
    assert tag == "obs" and outcomes == [("ok", 2), ("ok", 3)]
    # the worker's spans went with the chunk, not into the buffer
    assert obs.spans() == [parent_rec]
    # tuples survive pickling as plain sequences; ingest re-wraps them
    obs.ingest([tuple(r) for r in records])
    recs = obs.spans()
    assert len(recs) == 4
    child = next(r for r in recs if r.name == "pool.chunk")
    assert child.parent_id == parent_rec.span_id
    assert child.trace_id == parent_rec.trace_id
    assert child.args["tasks"] == 2
    assert {r.parent_id for r in recs if r.name == "task"} == {child.span_id}


def test_current_context_is_none_while_disabled():
    assert obs.current_context() is None


# ========================================================================
# exporters
# ========================================================================
def test_chrome_trace_is_valid_trace_event_json(tmp_path):
    obs.enable()
    with obs.span("engine.sweep", kind="exhaustive"):
        with obs.span("engine.exact"):
            pass
    trace = obs.chrome_trace()
    blob = json.dumps(trace)              # must be pure JSON values
    assert json.loads(blob) == trace
    events = trace["traceEvents"]
    assert trace["displayTimeUnit"] == "ms"
    xs = [e for e in events if e["ph"] == "X"]
    ms = [e for e in events if e["ph"] == "M"]
    assert {e["ph"] for e in events} <= {"X", "M"}
    assert len(xs) == 2
    for e in xs:
        assert set(e) == {"name", "cat", "ph", "ts", "dur", "pid", "tid",
                          "args"}
        assert e["dur"] >= 0 and "span_id" in e["args"]
    assert any(e["args"]["name"] == "repro" for e in ms)

    path = tmp_path / "trace.json"
    assert obs.write_trace(str(path)) == str(path)
    assert json.loads(path.read_text()) == trace


def test_summary_table_aggregates_by_span_name():
    assert "no spans recorded" in obs.summary()
    obs.enable()
    with obs.span("engine.sweep"):
        for _ in range(3):
            with obs.span("engine.task.walk", "task"):
                pass
    out = obs.summary()
    lines = out.splitlines()
    assert lines[0].split() == ["span", "count", "wall", "ms", "cpu", "ms",
                                "%", "top"]
    walk = next(ln for ln in lines if ln.startswith("engine.task.walk"))
    assert walk.split()[1] == "3"
    sweep = next(ln for ln in lines if ln.startswith("engine.sweep"))
    assert sweep.split()[-1] == "100.0"   # sole root defines the denominator


def test_trace_env_var_enables_and_dumps_at_exit(tmp_path):
    out = tmp_path / "env-trace.json"
    code = (
        "from repro import obs\n"
        "assert obs.enabled()\n"
        "with obs.span('engine.sweep', kind='exhaustive'):\n"
        "    pass\n"
    )
    env = dict(os.environ, PYTHONPATH=SRC, REPRO_TRACE_OUT=str(out))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    trace = json.loads(out.read_text())
    assert any(e.get("name") == "engine.sweep"
               for e in trace["traceEvents"])


# ========================================================================
# metrics registry
# ========================================================================
def test_counter_group_is_documented_dict_compatible_and_closed():
    g = CounterGroup("test.grp", {"alpha": "first", "beta": "second"},
                     register=False)
    g["alpha"] += 1
    g["alpha"] += 1
    g["beta"] = 5
    assert g["alpha"] == 2 and g.get("beta") == 5
    assert dict(g) == {"alpha": 2, "beta": 5}
    assert json.loads(json.dumps(g)) == {"alpha": 2, "beta": 5}
    assert any(g.values()) and set(g.keys()) == {"alpha", "beta"}
    assert len(g) == 2 and "alpha" in g
    with pytest.raises(KeyError, match="no declared counter"):
        g["gamma"] = 1
    with pytest.raises(KeyError, match="no declared counter"):
        g.update(gamma=1)
    g.reset()
    assert g.as_dict() == {"alpha": 0, "beta": 0}


def test_registry_documents_and_snapshots_attached_groups():
    import repro.core.gridwalk  # noqa: F401 — registers the core.* group

    g = CounterGroup("test.snap", {"hits": "probe hits"})
    try:
        specs = obs_metrics.describe()
        assert specs["test.snap.hits"].doc == "probe hits"
        assert specs["core.streams_built"].kind == "counter"
        assert specs["engine.sweep.evaluated"].unit == "count"
        g["hits"] += 3
        before = obs_metrics.snapshot()
        assert before["test.snap.hits"] == 3
        g["hits"] += 2
        assert obs_metrics.delta(before)["test.snap.hits"] == 2
        assert list(obs_metrics.snapshot()) == sorted(obs_metrics.snapshot())
    finally:
        obs_metrics.detach("test.snap")


def test_conflicting_metric_registration_raises():
    obs_metrics._register(MetricSpec("test.conflict.x", "counter", "count",
                                     "the original doc"))
    # identical re-registration is idempotent (module reloads, new groups)
    obs_metrics._register(MetricSpec("test.conflict.x", "counter", "count",
                                     "the original doc"))
    with pytest.raises(ValueError, match="already registered"):
        obs_metrics._register(MetricSpec("test.conflict.x", "counter",
                                         "count", "a different doc"))


def test_cache_stats_view_mirrors_historical_emission():
    metrics = {
        "engine.cache.hits": 7, "engine.cache.misses": 2,
        "engine.cache.entries": 9, "engine.cache.evictions": 0,
        "engine.sweep.pool_tasks": 4, "engine.sweep.bound_evals": 0,
        "engine.sweep.cells": 1, "engine.sweep.shared_cells": 0,
        "engine.sweep.evaluated": 3, "engine.sweep.pruned": 0,
        "core.streams_built": 1, "core.streams_shared": 0,
        "core.waves_folded": 0, "core.wave_fallbacks": 0,
        "pool.health.rebuilds": 0, "pool.health.retries": 0,
        "pool.health.hung_chunks": 0, "pool.health.broken_pools": 0,
        "pool.health.quarantined": 0,
    }
    view = cache_stats_view(metrics)
    # healthy pool: no pool_health key (historical behaviour), no flags
    assert set(view) == {"hits", "misses", "entries", "evictions",
                         "pool_tasks", "bound_evals", "cells",
                         "shared_cells", "evaluated", "pruned",
                         "streams_built", "streams_shared", "waves_folded",
                         "wave_fallbacks"}
    assert view["hits"] == 7 and view["streams_built"] == 1

    metrics["pool.health.rebuilds"] = 2
    view = cache_stats_view(metrics)
    assert view["pool_health"]["rebuilds"] == 2

    metrics["engine.axis.geometry_groups"] = 1
    metrics["serve.coalesced"] = 1
    view = cache_stats_view(metrics)
    assert view["geometry_groups"] == 1 and view["coalesced"] is True

    degraded = cache_stats_view({"engine.sweep.degraded": 1,
                                 "engine.sweep.bound_evals": 5,
                                 "engine.cache.hits": 1,
                                 "engine.cache.misses": 5})
    assert degraded == {"degraded": True, "hits": 1, "misses": 5,
                        "bound_evals": 5}


def test_every_view_key_is_in_the_frozen_schema():
    import repro.core.gridwalk  # noqa: F401 — registers the core.* group

    for key in ("hits", "pool_health", "degraded", "coalesced",
                "geometry_share"):
        assert key in CACHE_STATS_KEYS
    # and every canonical name the view reads is documented in the registry
    specs = obs_metrics.describe()
    for legacy, canon in CACHE_STATS_KEYS.items():
        if canon.endswith("*"):
            continue
        assert canon in specs, f"{legacy} -> {canon} undocumented"


# ========================================================================
# end to end: a real sweep, telemetry on vs off
# ========================================================================
def _rank(report):
    return [(e.config, e.estimate.perf_lups if e.estimate else None,
             e.limiter) for e in report.entries]


def test_sweep_spans_cover_pipeline_and_merge_worker_processes():
    from repro.core.engine import Explorer
    from repro.core.machines import A100
    from repro.core.selector import enumerate_gpu_configs
    from repro.core.specs import star_stencil_3d

    spec = star_stencil_3d(r=1, domain=(16, 24, 32))
    configs = enumerate_gpu_configs(256)

    off = Explorer(parallel=True, max_workers=2)._rank_gpu(
        spec, A100, configs, top_k=5)
    assert obs.spans() == []              # disabled sweep records nothing

    obs.enable()
    on = Explorer(parallel=True, max_workers=2)._rank_gpu(
        spec, A100, configs, top_k=5)
    recs = obs.spans()

    # rankings are bitwise identical with telemetry on or off
    assert _rank(on) == _rank(off)
    assert on.cache_stats == off.cache_stats

    names = {r.name for r in recs}
    assert {"engine.sweep", "engine.bounds", "engine.refine",
            "engine.rank", "pool.run"} <= names
    sweep = next(r for r in recs if r.name == "engine.sweep")
    assert sweep.args["kind"] == "pruned"
    # every phase span nests under the sweep root
    by_id = {r.span_id: r for r in recs}
    for r in recs:
        if r.name in ("engine.bounds", "engine.refine", "engine.rank"):
            assert r.parent_id == sweep.span_id
            assert sweep.t0_us <= r.t0_us
            assert r.t0_us + r.dur_us <= sweep.t0_us + sweep.dur_us + 1.0
    # worker chunks (when a pool actually forked) carry their own pid and
    # parent into a pool.run span recorded in the parent process
    chunks = [r for r in recs if r.name == "pool.chunk"]
    for c in chunks:
        assert c.pid != os.getpid()
        assert by_id[c.parent_id].name == "pool.run"
        assert by_id[c.parent_id].pid == os.getpid()
    if chunks:   # serial fallback (no usable start method) skips workers
        assert {r.pid for r in recs} - {os.getpid()}
        tasks = [r for r in recs if r.cat == "task"]
        assert tasks and all(r.pid != os.getpid() for r in tasks)


def test_report_metrics_carry_canonical_names():
    from repro.core.engine import Explorer
    from repro.core.machines import A100
    from repro.core.selector import enumerate_gpu_configs
    from repro.core.specs import star_stencil_3d

    rep = Explorer()._rank_gpu(star_stencil_3d(r=1, domain=(16, 24, 32)),
                               A100, enumerate_gpu_configs(128), top_k=3)
    assert rep.metrics["engine.sweep.cells"] == 1
    assert rep.metrics["engine.sweep.evaluated"] >= len(rep.entries)
    assert rep.metrics["engine.sweep.pruned"] == len(rep.pruned)
    assert rep.cache_stats == cache_stats_view(rep.metrics)
    # the view and the canonical mapping agree value-for-value
    for legacy, value in rep.cache_stats.items():
        canon = CACHE_STATS_KEYS[legacy]
        if not canon.endswith("*"):
            assert rep.metrics[canon] == value
