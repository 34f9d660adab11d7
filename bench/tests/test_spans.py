"""The readers of the program's own spans: on a synthetic record of two
requests, where every number is known, and in a traced CPU run of each
pricing cell; and nothing read where the program recorded nothing."""
import types

import pytest

from bench.harness import cell
from bench.harness import spans as sp

from .conftest import cells

READERS = ("engine_ms", "persist_ms", "wire_ms", "gc_ms")
CLIENT, DAEMON = 10, 20
OFFSET = -5e6           # trace clock minus monotonic clock, ns
LO, HI = 500.0, 60000.0  # the window, monotonic us


def rec(name, sid, parent, t0, dur, pid=CLIENT, **args):
    """A SpanRecord field by field (times in us)."""
    from repro.obs import SpanRecord

    return SpanRecord(name, "t", "trace", sid, parent, pid, 1, t0, dur,
                      0.0, args)


def synthetic() -> list:
    """Two requests: a fast one (10 ms, a journal fsync, a daemon
    collection) and a slow one (30 ms, a compaction); a client collection
    between them, and a request after the window."""
    r = [
        rec("frontend.candidates", "c.1", None, 800, 150, candidates=4),
        rec("client.price", "c.2", None, 1000, 10000, spans_us=200.0),
        rec("client.encode", "c.3", "c.2", 1010, 100),
        rec("client.wait", "c.4", "c.2", 1110, 9700),
        rec("client.decode", "c.5", "c.2", 10810, 150),
        rec("serve.request", "d.1", "c.2", 1200, 8000, DAEMON),
        rec("serve.price", "d.2", "d.1", 1500, 6500, DAEMON),
        rec("engine.sweep", "d.3", "d.2", 1600, 6000, DAEMON),
        rec("engine.save_cache", "d.4", "d.3", 6500, 1000, DAEMON),
        rec("durable.append", "d.5", "d.4", 6600, 800, DAEMON,
            fsync_us=500.0, bytes=1000),
        rec("gc.collect", "d.6", "d.3", 2000, 500, DAEMON, generation=0,
            collected=3),
        rec("gc.collect", "c.6", None, 15000, 2000, generation=2,
            collected=100),
        rec("frontend.candidates", "c.7", None, 18000, 250, candidates=4),
        rec("client.price", "c.8", None, 20000, 30000, spans_us=400.0),
        rec("serve.request", "d.7", "c.8", 20300, 29000, DAEMON),
        rec("serve.price", "d.8", "d.7", 20500, 26000, DAEMON),
        rec("engine.sweep", "d.9", "d.8", 20600, 25000, DAEMON),
        rec("engine.save_cache", "d.10", "d.9", 35600, 10000, DAEMON),
        rec("cache.compaction", "d.11", "d.10", 35700, 9000, DAEMON),
        rec("client.price", "c.9", None, 100000, 5000),
    ]
    return r


def reduced(latency_s=(0.012, 0.032)):
    records = synthetic()
    starts = {r.span_id: (r.t0_us * 1e3 + OFFSET + 1e3 * (i % 3), 2.0)
              for i, r in enumerate(records) if r.pid == CLIENT}
    # a collection between the two readings of a pair: 0.3 ms off, and
    # known to be loose
    starts["c.3"] = (starts["c.3"][0] + 3e5, 320.0)
    return sp.reduce(records, starts, LO * 1e3 + OFFSET, HI * 1e3 + OFFSET,
                     latency_s=list(latency_s), serve_s=[0.0101, 0.0301],
                     trace_s=[0.0002, 0.0002])


def read(name, w):
    run = types.SimpleNamespace(record={"obs_window": w})
    return cell.metric_reader(name).read(run)


def test_the_split_of_two_requests():
    w = reduced()
    assert w["n"] == 2 and [r["served"] for r in w["rows"]] == [1, 1]
    assert w["offset_spread_ns"] == pytest.approx(2e3)
    assert w["loose_us"] == [320.0] and w["astray"] == []
    engine, note = read("engine_ms", w)
    assert engine == pytest.approx(((6 - 1) + (25 - 10)) / 2)
    assert "2 engine.sweep spans in 2 requests" in note
    persist, note = read("persist_ms", w)
    assert persist == pytest.approx((1 + 10) / 2)
    assert "1 cache.compaction spans, first 9.0 ms, last 9.0 ms" in note
    assert "1 durable.append fsyncs, mean 0.5 ms" in note
    # the slow request is the one above p95, and it compacted
    assert "above p95: 1 requests, 1 with a compaction" in note
    wire, note = read("wire_ms", w)
    assert wire == pytest.approx(((10 - 6 - 0.2) + (30 - 25 - 0.4)) / 2)
    # the three parts and the daemon's rendering of the spans add up to
    # the mean client.price
    assert engine + persist + wire + 0.3 == pytest.approx((10 + 30) / 2)
    assert "serve.request per request 1-1" in note
    assert f"rendering the spans {(0.2 + 0.4) / 2!r} ms" in note
    assert "1 pairs wider than 50.0 us left out, the widest 320.0" in note
    assert "frontend.candidates 0.2 ms per request" in note
    gc_ms, note = read("gc_ms", w)
    assert gc_ms == pytest.approx((0.5 + 2) / 2)
    assert "client gen 2: 1 collections, 2.0 ms" in note
    assert "daemon gen 0: 1 collections, 0.5 ms" in note


def test_a_shared_sweep_counts_once_and_tail_pairs_by_client_time():
    records = synthetic()
    # the second request joined the first one's sweep: the same spans
    # come back beneath both, tagged with the two requests they served
    records += [r._replace(parent_id="d.7") for r in records
                if r.span_id == "d.2"]
    starts = {r.span_id: (r.t0_us * 1e3 + OFFSET, 2.0) for r in records
              if r.pid == CLIENT}
    w = sp.reduce(records, starts, LO * 1e3 + OFFSET, HI * 1e3 + OFFSET,
                  latency_s=[0.1])             # does not pair: by span time
    assert w["sweeps"] == 2
    assert w["engine_ms"] == pytest.approx(((6 - 1) + (25 - 10)) / 2)
    assert len(sp.tail(w, 95)) == 1 and sp.tail(w, 95)[0]["ms"] == 30.0


@pytest.mark.parametrize("name", READERS)
def test_nothing_is_read_where_the_program_recorded_nothing(name):
    from repro import obs

    obs.reset()
    untraced = types.SimpleNamespace(record={"trace": None})
    traced = types.SimpleNamespace(record={"trace": {"lo": 0, "hi": 1}})
    assert cell.metric_reader(name).read(untraced) is None
    assert cell.metric_reader(name).read(traced) is None
    assert sp.reduce([], {}, 0, 1) is None
    assert sp.reduce(synthetic(), {}, 0, 1) is None     # no mirrored pairs


@pytest.mark.parametrize("name", cells("closed_cold"))
def test_a_traced_cpu_run_reads_all_four(cpu_harness, name):
    from repro import obs

    obs.reset()
    out, run = cpu_harness(name, trace=True, seconds=1.0)
    assert out["correct"] is True
    assert set(READERS) <= set(out["metrics"])
    w = run.record["obs_window"]
    assert w["n"] == len(run.record["latency_s"])
    assert all(r["served"] == 1 for r in w["rows"])
    assert w["offset_spread_ns"] < 1e5
    split = sum(out["metrics"][m]["value"]
                for m in ("engine_ms", "persist_ms", "wire_ms"))
    assert split <= out["metrics"]["serve_ms"]["value"]
    obs.reset()
    out, run = cpu_harness(name, trace=False)
    assert not set(READERS) & set(out["metrics"])
    assert obs.spans() == []
