"""Request-scoped spans (DESIGN.md §14): a recording client's context
crosses the socket, the daemon records that request alone and returns its
spans in the result line; recording follows the JAX profiler and mirrors
onto its timeline; collector pauses and journal fsyncs are spans; and a
request without context is answered exactly as before.
"""
import gc
import glob
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import pytest

from repro import durable, obs
from repro.api import gpu_request
from repro.core.access import LaunchConfig
from repro.core.engine import Explorer
from repro.core.machines import GPUMachine
from repro.core.specs import star_stencil_3d
from repro.serve import PriceClient, PricingDaemon
from repro.serve.daemon import can_bind_unix_sockets
from repro.serve.schema import SCHEMA_VERSION, encode

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")
SMALL = GPUMachine(
    name="A100/8", n_sms=13, clock_hz=1.41e9, l1_bytes=192 * 1024,
    l2_bytes=20 * 1024 * 1024 // 8, dram_bw=1400e9 / 8, l2_bw=5000e9 / 8,
    peak_flops_dp=9.7e12 / 8,
)
CONFIGS = [LaunchConfig(block=b) for b in [(64, 4, 2), (32, 4, 4)]]

needs_sockets = pytest.mark.skipif(
    not can_bind_unix_sockets(os.environ.get("TMPDIR", "/tmp")),
    reason="environment cannot bind Unix sockets")


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _request(domain):
    return gpu_request(star_stencil_3d(r=1, domain=domain), SMALL, CONFIGS)


def _boot_daemon(tmp_path, *args):
    """A ``python -m repro.serve`` child (with ``args``) with a persistent
    cache and its own collection off; returns (process, socket path)."""
    sock = str(tmp_path / "s.sock")
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("REPRO_TRACE_OUT", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.serve", "--socket", sock,
         "--cache-path", str(tmp_path / "cache.inv"), *args],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    for _ in range(600):
        if os.path.exists(sock):
            return proc, sock
        if proc.poll() is not None:
            break
        time.sleep(0.05)
    proc.kill()
    raise RuntimeError("daemon never bound: " + proc.stdout.read())


def _stop(proc):
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


def _ancestors(rec, by_id):
    out = []
    while rec.parent_id in by_id:
        rec = by_id[rec.parent_id]
        out.append(rec)
    return out


# ========================================================================
# across the socket
# ========================================================================
@needs_sockets
def test_daemon_spans_come_back_under_client_price(tmp_path):
    proc, sock = _boot_daemon(tmp_path)
    try:
        with PriceClient(sock, timeout=120) as c:
            obs.enable()                 # the client records; the daemon not
            c.price(_request((16, 24, 32)))     # cold: base blob written
            c.price(_request((24, 24, 32)))     # cold: journal segment
            c.price(_request((24, 24, 32)))     # warm: memo hit
            obs.disable()
            daemon_trace = c.trace()
    finally:
        _stop(proc)
    recs = obs.spans()
    by_id = {r.span_id: r for r in recs}
    roots = [r for r in recs if r.name == "client.price"]
    served = [r for r in recs if r.name == "serve.request"]
    assert len(roots) == 3 and len(served) == 3
    # one serve.request per request, each the child of its client.price
    assert sorted(r.parent_id for r in served) == sorted(
        r.span_id for r in roots)
    assert {r.pid for r in served} == {proc.pid}
    for r in recs:
        if r.pid == proc.pid and r.name != "serve.request":
            chain = _ancestors(r, by_id)
            assert [a.name for a in chain][-2:] == ["serve.request",
                                                    "client.price"], r
            assert r.trace_id == roots[0].trace_id
    names = [r.name for r in recs]
    for name in ("client.encode", "client.wait", "client.decode",
                 "serve.decode", "serve.encode", "serve.send"):
        assert names.count(name) == 3, name
    # the memo hit is neither queued nor priced
    assert names.count("serve.queue") == 2
    assert names.count("serve.price") == 2
    assert names.count("engine.sweep") == 2
    assert names.count("engine.save_cache") == 2
    (append,) = [r for r in recs if r.name == "durable.append"]
    assert append.args["fsync_us"] >= 0 and append.args["bytes"] > 0
    assert by_id[append.parent_id].name == "engine.save_cache"
    for root in roots:
        assert root.args["bytes_out"] > 0 and root.args["bytes_in"] > 0
        # the daemon's rendering of the spans, timed apart
        assert root.args["spans_us"] > 0
        # parsed and ingested once client.price had closed: the daemon's
        # records follow the client's in the buffer
        (mine,) = [r for r in served if r.parent_id == root.span_id]
        assert recs.index(root) < recs.index(mine)
    # the daemon's own collection stayed off: its buffer is empty
    assert daemon_trace["traceEvents"] == []


@needs_sockets
def test_pool_workers_ship_only_their_own_requests_spans(tmp_path):
    """A daemon pricing on a worker pool, asked traced, untraced and traced
    again: each traced line carries its own request's spans alone, its
    pool chunks among them, and nothing from the requests between."""
    proc, sock = _boot_daemon(tmp_path, "--parallel", "--max-workers", "2")
    domains = [(16, 24, 32), (24, 24, 32), (32, 24, 32), (40, 24, 32)]
    try:
        with PriceClient(sock, timeout=120) as c:
            for i, domain in enumerate(domains):
                if i in (0, 3):
                    obs.enable()
                c.price(_request(domain))
                obs.disable()
            daemon_trace = c.trace()
    finally:
        _stop(proc)
    recs = obs.spans()
    by_id = {r.span_id: r for r in recs}
    roots = [r for r in recs if r.name == "client.price"]
    assert len(roots) == 2
    for root in roots:
        below = [r for r in recs
                 if r.pid != os.getpid() and root in _ancestors(r, by_id)]
        chunks = [r for r in below if r.name == "pool.chunk"]
        assert chunks and all(r.pid not in (proc.pid, os.getpid())
                              for r in chunks)
        assert all(by_id[r.parent_id].name == "pool.run" for r in chunks)
    # every record the daemon and its workers sent has its request above
    daemon_side = [r for r in recs if r.pid != os.getpid()]
    assert all(_ancestors(r, by_id)[-1] in roots for r in daemon_side)
    assert daemon_trace["traceEvents"] == []


def test_a_worker_records_nothing_past_its_chunk():
    """A persistent pool worker, in-process: a traced chunk, an untraced
    one, a traced one.  Recording ends with each traced chunk; the untraced
    chunk's spans go nowhere, and the last chunk ships only its own."""
    from repro.core.engine.pool import _pool_batch

    def task(x):
        with obs.span("task", "task"):
            return x

    calls = [(task, (1,)), (task, (2,))]
    _, _, first = _pool_batch(calls, ("t1", "p1"))
    assert _pool_batch(calls, None) == [("ok", 1), ("ok", 2)]
    assert obs.spans() == [] and not obs.enabled()
    assert obs.span("x") is obs.span("y")        # the shared no-op
    _, _, last = _pool_batch(calls, ("t2", "p2"))
    for records, trace_id, parent in ((first, "t1", "p1"),
                                      (last, "t2", "p2")):
        assert sorted(r.name for r in records) == ["pool.chunk", "task",
                                                   "task"]
        assert {r.trace_id for r in records} == {trace_id}
        (chunk,) = [r for r in records if r.name == "pool.chunk"]
        assert chunk.parent_id == parent
    assert obs.spans() == []


@needs_sockets
def test_a_request_without_context_is_answered_as_before(tmp_path):
    sock = str(tmp_path / "serve.sock")
    req = _request((16, 24, 32))
    body = {"op": "price", "id": 7, "request": encode(req),
            "schema_version": SCHEMA_VERSION}

    def ask(msg):
        with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as s:
            s.settimeout(120)
            s.connect(sock)
            s.sendall((json.dumps(msg) + "\n").encode())
            return s.makefile("rb").readline()

    sent = []
    with PricingDaemon(sock, engine=Explorer(parallel=False)):
        plain = ask(body)
        traced = ask(dict(body, obs=["t", "1.1"]))
        again = ask(body)
        with PriceClient(sock, timeout=120) as c:
            real = c._send_bytes
            c._send_bytes = lambda data: (sent.append(data), real(data))[1]
            c.price(req)             # nothing records: no context sent
    assert plain == again
    assert b'"spans"' not in plain and b'"obs"' not in sent[0]
    # the traced line is the plain one with the spans and the time they
    # took to render appended last
    head, tail = traced.split(b',"spans":', 1)
    assert head + b"}\n" == plain
    shipped = json.loads(b'{"spans":' + tail)
    assert list(shipped) == ["spans", "spans_us"]
    assert [s[0] for s in shipped["spans"]][-1] == "serve.request"
    assert shipped["spans_us"] > 0
    assert obs.spans() == []


def test_a_shared_sweep_ships_with_every_request_tagged():
    a = obs.Scope(("ta", "client-a"))
    b = obs.Scope(("tb", "client-b"))
    assert obs.collect([]) is obs.span("anything")   # one check, no-op
    with obs.collect([a, b], requests=2):
        with obs.span("serve.coalesce", "serve"):
            with obs.span("engine.sweep"):
                pass
    for sc, trace_id in ((a, "ta"), (b, "tb")):
        recs = {r.name: r for r in sc.close("serve.request", "serve")}
        assert recs["serve.coalesce"].parent_id == sc.root_id
        assert recs["engine.sweep"].parent_id == recs["serve.coalesce"].span_id
        assert recs["serve.request"].parent_id == f"client-{trace_id[1]}"
        assert all(recs[n].args["requests"] == 2
                   for n in ("serve.coalesce", "engine.sweep"))
        assert {r.trace_id for r in recs.values()} == {trace_id}
    assert obs.spans() == []


def test_a_scope_records_across_threads_and_only_its_own_spans():
    sc = obs.Scope(("t", "p"))
    with obs.span("outside"):            # nothing records here
        pass
    queued = time.perf_counter_ns()

    def worker():
        with sc.active():
            assert obs.current_context() == ("t", sc.root_id)
            with obs.span("serve.price", "serve"):
                pass

    th = threading.Thread(target=worker)
    th.start()
    th.join()
    sc.stamp("serve.queue", "serve", queued, time.perf_counter_ns())
    with obs.span("after"):
        pass
    recs = sc.close("serve.request", "serve")
    assert [r.name for r in recs] == ["serve.price", "serve.queue",
                                      "serve.request"]
    assert all(r.parent_id == sc.root_id for r in recs[:2])
    assert recs[0].tid != recs[-1].tid
    assert obs.spans() == [] and obs.current_context() is None


# ========================================================================
# the collector and the journal
# ========================================================================
def test_gc_spans_record_inside_a_scope_and_not_outside_one():
    gc.collect()
    obs.enable()                      # process-wide alone: no gc spans
    gc.collect()
    obs.disable()
    assert not [r for r in obs.spans() if r.name == "gc.collect"]
    sc = obs.Scope(("t", None))
    gc.collect(1)
    recs = sc.close("serve.request", "serve")
    gcs = [r for r in recs if r.name == "gc.collect"]
    assert len(gcs) == 1
    assert gcs[0].args["generation"] == 1 and gcs[0].args["collected"] >= 0
    assert gcs[0].parent_id == sc.root_id and gcs[0].dur_us >= 0
    gc.collect()                      # closed: nothing more
    assert [r.name for r in recs].count("gc.collect") == 1


def test_a_collection_under_the_buffer_lock_does_not_deadlock(tmp_path):
    """While the profiler captures, a collection can start inside the
    records lock of the same thread (``obs.spans()`` copies the buffer):
    its span must not wait for that lock."""
    import jax

    done = []

    def work():
        old = gc.get_threshold()
        with jax.profiler.trace(str(tmp_path)):
            gc.set_threshold(1)
            try:
                for _ in range(50):
                    with obs.span("x"):
                        pass
                    obs.spans()
            finally:
                gc.set_threshold(*old)
        done.append(True)

    th = threading.Thread(target=work, daemon=True)
    th.start()
    th.join(timeout=60)
    if th.is_alive():       # deadlocked: free the buffer for what follows
        sys.modules["repro.obs.spans"]._lock = threading.Lock()
    assert done and not th.is_alive()
    assert any(r.name == "gc.collect" for r in obs.spans())


def test_durable_append_carries_fsync_us(tmp_path):
    journal = durable.Journal(str(tmp_path / "j"))
    journal.append(b"unrecorded")
    sc = obs.Scope(("t", None))
    with sc.active():
        journal.append(b"x" * 100)
    (rec,) = [r for r in sc.close("serve.request") if r.name != "serve.request"]
    assert rec.name == "durable.append"
    assert rec.args["bytes"] == 100 + durable.FRAME_OVERHEAD
    assert 0 <= rec.args["fsync_us"] <= rec.dur_us
    assert obs.spans() == []
    assert durable.scan(journal.path)[0] == [b"unrecorded", b"x" * 100]


# ========================================================================
# the JAX profiler
# ========================================================================
def test_spans_follow_the_profiler_and_mirror_onto_its_timeline(
        tmp_path, monkeypatch):
    import types

    import jax
    from jax.profiler import ProfileData

    from repro.kernels.matmul.generator import candidate_specs

    spans_mod = sys.modules["repro.obs.spans"]   # the module, not obs.spans()

    # a pid whose hex digits look like a number (0x127): ids such as
    # "127:10" must come back from the trace as the same strings
    monkeypatch.setattr(spans_mod, "os", types.SimpleNamespace(
        getpid=lambda: 0x127))
    monkeypatch.setattr(spans_mod, "_owner_pid", 0x127)
    with obs.span("before"):
        pass
    assert obs.profiling() is False and obs.spans() == []
    with jax.profiler.trace(str(tmp_path)):
        assert obs.profiling() is True
        with obs.span("outer", "test"):
            pairs = tuple(candidate_specs(256, 512, 384))
        for _ in range(12):
            with obs.span("inner", "test"):
                pass
        gc.collect()
    with obs.span("after"):
        pass
    recs = obs.spans()
    names = [r.name for r in recs]
    assert names.count("outer") == 1 and "before" not in names
    assert "after" not in names and "gc.collect" in names
    (cand,) = [r for r in recs if r.name == "frontend.candidates"]
    assert cand.args["candidates"] == len(pairs) > 0
    by_id = {r.span_id: r for r in recs}
    assert by_id[cand.parent_id].name == "outer"

    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    events[dict(e.stats)["span_id"]] = (e, dict(e.stats))
    assert {events[r.span_id][0].name for r in recs} == {
        f"repro.{r.name}" for r in recs}
    offsets = []
    assert all(r.span_id.startswith("127:") for r in recs)
    for r in recs:
        e, stats = events[r.span_id]
        assert stats["obs_t0_us"] == pytest.approx(r.t0_us, abs=1e-3)
        # the annotation opened between the pair's two readings
        assert stats["obs_pair_us"] >= 0
        offsets.append(e.start_ns - r.t0_us * 1e3)
    # one clock offset places every record on the profiler's timeline
    assert max(offsets) - min(offsets) < 1e6


# ========================================================================
# kernel names
# ========================================================================
def test_kernels_carry_their_name_and_the_tracer_ignores_it():
    import jax
    import jax.numpy as jnp

    from repro.frontend import arg, trace_kernel
    from repro.kernels import pallas_call
    from repro.kernels.stencil3d25.kernel import make_ring

    call = make_ring(1, (8, 16, 128), (1.0,) * 7, jnp.float32)
    jaxpr = str(jax.make_jaxpr(call)(jnp.zeros((10, 18, 130), jnp.float32)))
    assert "stencil3d25_ring" in jaxpr

    from jax.experimental import pallas as pl

    def copy(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def builder(name):
        def call(x):
            return pallas_call(
                copy, name=name, grid=(2,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32))(x)
        return call

    args = (arg("x", (16, 128), jnp.float32),)
    named = trace_kernel(builder("copy_tiled"), args, name="k")
    unnamed = trace_kernel(builder(None), args, name="k")
    assert repr(named) == repr(unnamed)


def test_scopes_on_many_threads_keep_their_own_spans():
    """More threads than cores, a short switch interval and collections
    on every thread: each request's scope holds its own spans and the
    pauses, and nothing leaks into another scope or the process buffer."""
    n_threads, per_thread = 4 * (os.cpu_count() or 2), 20
    scopes = [obs.Scope((f"t{i}", f"c{i}")) for i in range(n_threads)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(i):
            for j in range(per_thread):
                with scopes[i].active():
                    with obs.span("serve.price", "serve", i=i):
                        with obs.span("engine.sweep", i=i):
                            if j % 5 == 0:
                                gc.collect(0)

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    for i, sc in enumerate(scopes):
        recs = sc.close("serve.request", "serve")
        mine = [r for r in recs if r.name in ("serve.price", "engine.sweep")]
        assert len(mine) == 2 * per_thread
        assert {r.args["i"] for r in mine} == {i}
        assert {r.trace_id for r in recs} == {f"t{i}"}
        by_id = {r.span_id: r for r in recs}
        for r in mine:
            parent = by_id[r.parent_id]
            assert parent.name in ("serve.price", "serve.request")
        # every scope was open for its own thread's collections
        assert sum(r.name == "gc.collect" for r in recs) >= per_thread // 5
    assert obs.spans() == []
