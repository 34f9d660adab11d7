"""Crash-resume soak: the durability gate (DESIGN.md §15).

Drives the crash-consistency machinery end to end and checks the
durability invariant: **a process killed at any instant loses at most the
cell that was mid-commit, and a resumed process reproduces the exact
answers of a never-killed run while re-pricing (almost) nothing.**

Four phases, each emitting deterministic gates into
``BENCH_crash_resume.json`` (checked by ``scripts/check_bench.py``):

  A. **fault-free reference** — every request priced serially; the
     rankings are the ground truth every later phase compares against.
  B. **SIGKILL storm** — a child process prices the whole request list
     with ``Explorer(resume=...)`` under a ``proc.kill`` plan that
     SIGKILLs it at its first checkpoint commit.  Each storm run makes
     exactly one cell of durable progress and dies; the next run resumes
     everything committed.  After the storm a clean verification run must
     restore every cell from the journal (zero live pricing) and rank
     bitwise-identically to phase A.
  C. **torn cache journal** — ``io.torn_write`` makes an invariant-cache
     save half-write its journal segment and *report success* (the lying
     filesystem).  The next load must detect the tear, quarantine the
     tail, keep every earlier commit, and re-price bitwise-identically.
  D. **daemon restart** — a real ``python -m repro.serve`` process with
     ``--cache-path/--resume/--pid-file`` is SIGKILL'd after serving the
     batch; a client with retries constructed against the dead socket
     rides the restart window; the restarted daemon restores its memo
     journal, answers warm (single-digit-ms p50) and bitwise-identically,
     and a SIGTERM drains it cleanly (exit 0, pid file removed).

Like the chaos soak, the bench re-execs itself into a clean interpreter
if jax is already loaded (jax forces the forkserver start method).
"""
from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

from repro import durable, faults
from repro.api import gpu_request, price
from repro.core.engine import Explorer
from repro.core.engine.pool import host_env
from repro.core.specs import star_stencil_3d
from repro.serve import PriceClient
from repro.serve.daemon import can_bind_unix_sockets

from .common import SMALL_A100, bench_json, configs_512, emit

DOMAINS = [(16, 24, 32), (24, 24, 32), (16, 32, 32),
           (24, 32, 32), (16, 24, 48), (24, 32, 48)]
WARM_PROBES = 20


def distinct_requests():
    configs = configs_512()[:6]
    return [gpu_request(star_stencil_3d(r=1, domain=d), SMALL_A100, configs)
            for d in DOMAINS]


def ranking_key(result):
    return [(e.workload, e.machine, e.index, e.perf, e.limiter)
            for e in result.entries]


def _src_env():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = host_env()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    return root, env


# ------------------------------------------------------------------------
# phase B: SIGKILL storm against the sweep checkpoint journal
# ------------------------------------------------------------------------
def _child_main(ckpt: str, out: str) -> None:
    """One storm run: price every request against the shared resume
    journal; under ``proc.kill at=(0,)`` this commits exactly one new
    cell and dies at its fsync."""
    faults.ensure_env_plan()
    engine = Explorer(parallel=False, resume=ckpt)
    fps, resumed, live = [], 0, 0
    t0 = time.perf_counter()
    for req in distinct_requests():
        res = price(req, engine=engine)
        fps.append(ranking_key(res))
        m = res.report.metrics
        r = int(m.get("engine.sweep.resumed_cells", 0))
        resumed += r
        live += int(m.get("engine.sweep.cells", 0)) - r
        print(f"# progress resumed={resumed} live={live}", flush=True)
    durable.atomic_write(out, json.dumps({
        "fps": fps, "resumed": resumed, "live": live,
        "price_s": time.perf_counter() - t0}))


def phase_kill_storm(tmp, references):
    ckpt = os.path.join(tmp, "storm.sweeps")
    out = os.path.join(tmp, "storm.json")
    root, env = _src_env()
    cmd = [sys.executable, "-m", "benchmarks.bench_crash_resume",
           "--child", ckpt, out]
    n_cells = len(references)

    kill_env = dict(env, REPRO_FAULT_PLAN=json.dumps(
        {"seed": 1, "faults": {"proc.kill": {"at": [0]}}}))
    runs = kills = non_sigkill = storm_live = 0
    completed = False
    t0 = time.perf_counter()
    while runs < n_cells * 2 + 2:       # hard stop: a storm must converge
        proc = subprocess.run(cmd, env=kill_env, cwd=root,
                              capture_output=True, text=True)
        runs += 1
        if proc.returncode == 0:
            completed = True            # all cells resumed, nothing left
            break                       # for the kill plan to interrupt
        if proc.returncode != -signal.SIGKILL:
            non_sigkill += 1
            break
        kills += 1
        # cells priced live before the kill (the killed cell never prints)
        last = [ln for ln in proc.stdout.splitlines()
                if ln.startswith("# progress")]
        storm_live += (int(last[-1].rsplit("live=", 1)[1]) if last else 0)
    storm_s = time.perf_counter() - t0

    # clean verification run: everything must come back from the journal
    proc = subprocess.run(cmd, env=env, cwd=root, capture_output=True,
                          text=True)
    verified = json.load(open(out)) if proc.returncode == 0 \
        and os.path.exists(out) else {"fps": [], "resumed": -1, "live": -1}
    # references crossed the JSON wire in the child: normalize tuples
    wire_refs = json.loads(json.dumps(references))
    total_live = storm_live + max(verified["live"], 0)
    # the storm commits one cell per kill: everything beyond n_cells of
    # live pricing across the whole storm is duplicated (lost) work
    repriced_fraction = max(0, total_live - n_cells) / n_cells
    return {
        "storm_runs": runs,
        "storm_all_sigkilled": (non_sigkill == 0 and completed
                                and kills == n_cells),
        "storm_identical": verified["fps"] == wire_refs,
        "resumed_all": (verified["resumed"] == n_cells
                        and verified["live"] == 0),
        "repriced_fraction": repriced_fraction,
        "repriced_ok": repriced_fraction <= 0.10,
        "storm_s": storm_s,
        "resumed_price_s": verified.get("price_s", float("nan")),
    }


# ------------------------------------------------------------------------
# phase C: torn invariant-cache journal segment
# ------------------------------------------------------------------------
def phase_torn_journal(tmp, requests, references):
    cache_path = os.path.join(tmp, "torn.invcache")
    base = Explorer(parallel=False, cache_path=cache_path)
    assert ranking_key(price(requests[0], engine=base)) == references[0]

    liar = Explorer(parallel=False, cache_path=cache_path)
    with faults.injected(faults.FaultPlan(seed=3, faults={
            "io.torn_write": faults.FaultSpec(at=(0,))})):
        # the save under this sweep half-writes its segment, reports OK
        assert ranking_key(price(requests[1], engine=liar)) == references[1]

    healed = Explorer(parallel=False, cache_path=cache_path)
    torn_detected = healed.cache.health["journal_torn"] == 1
    tail_quarantined = os.path.exists(cache_path + ".journal.tail")
    kept_base = healed.cache.loaded_entries > 0
    identical = ranking_key(price(requests[1], engine=healed)) \
        == references[1]
    rebuilt = Explorer(parallel=False,
                       cache_path=cache_path).cache.health["journal_torn"] \
        == 0
    return {
        "torn_detected": torn_detected,
        "torn_tail_quarantined": tail_quarantined,
        "torn_kept_committed_prefix": kept_base,
        "torn_reprice_identical": identical,
        "torn_journal_healed": rebuilt,
    }


# ------------------------------------------------------------------------
# phase D: daemon SIGKILL + --resume restart, client rides the window
# ------------------------------------------------------------------------
def phase_daemon_restart(tmp, requests, references):
    sock = os.path.join(tmp, "restart.sock")
    cache = os.path.join(tmp, "restart.invcache")
    pidfile = os.path.join(tmp, "restart.pid")
    root, env = _src_env()
    cmd = [sys.executable, "-m", "repro.serve", "--socket", sock,
           "--cache-path", cache, "--resume", "--pid-file", pidfile]

    def boot():
        proc = subprocess.Popen(cmd, env=env, cwd=root,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)
        for _ in range(600):
            if os.path.exists(sock):
                return proc
            if proc.poll() is not None:
                break
            time.sleep(0.05)
        raise RuntimeError("daemon never bound: " + proc.stdout.read())

    first = boot()
    try:
        t0 = time.perf_counter()
        with PriceClient(sock, retries=0, timeout=600) as client:
            cold = [ranking_key(r) for r in client.price_many(requests)]
        cold_s = time.perf_counter() - t0
        pid_ok = int(open(pidfile).read()) == first.pid
        os.kill(first.pid, signal.SIGKILL)
        first.wait(timeout=60)

        # constructed against the DEAD socket: the deferred connect plus
        # the retry budget must carry it across the restart window
        rider = PriceClient(sock, retries=12, backoff_s=0.2, timeout=600)
        second = boot()
        try:
            warm = [ranking_key(r) for r in rider.price_many(requests)]
            stats = rider.stats()
            lats = []
            for _ in range(WARM_PROBES):
                t0 = time.perf_counter()
                rider.price(requests[0])
                lats.append((time.perf_counter() - t0) * 1e3)
            lats.sort()
            warm_p50_ms = lats[len(lats) // 2]
            rider.close()
        finally:
            os.kill(second.pid, signal.SIGTERM)
            sigterm_rc = second.wait(timeout=60)
    finally:
        if first.poll() is None:
            first.kill()
    return {
        "restart_pidfile_ok": pid_ok,
        "restart_identical": cold == references and warm == references,
        "restart_memo_restored": stats["memo_restored"] >= len(requests),
        "restart_answered_warm": stats["memo_hits"] >= len(requests),
        "restart_client_rode_window": True,     # price_many above returned
        "restart_warm_p50_ok": warm_p50_ms < 10.0,
        "warm_p50_ms": warm_p50_ms,
        "sigterm_clean": sigterm_rc == 0 and not os.path.exists(pidfile),
        "cold_batch_s": cold_s,
    }


def _main_impl():
    tmp = tempfile.mkdtemp(prefix="bench-crash-")
    try:
        if not can_bind_unix_sockets(tmp):
            raise RuntimeError("environment cannot bind Unix sockets; "
                               "crash-resume soak needs a real socket")
        os.environ.pop(faults.ENV_VAR, None)
        faults.clear()

        requests = distinct_requests()
        t0 = time.perf_counter()
        references = [ranking_key(price(r)) for r in requests]
        ref_s = time.perf_counter() - t0

        storm = phase_kill_storm(tmp, references)
        torn = phase_torn_journal(tmp, requests, references)
        restart = phase_daemon_restart(tmp, requests, references)

        emit("crash_resume/reference", ref_s * 1e6,
             f"cells={len(requests)}")
        emit("crash_resume/kill_storm", storm["storm_s"] * 1e6,
             f"runs={storm['storm_runs']};"
             f"identical={storm['storm_identical']};"
             f"repriced_fraction={storm['repriced_fraction']:.2f}")
        emit("crash_resume/torn_journal", 0.0,
             f"detected={torn['torn_detected']};"
             f"identical={torn['torn_reprice_identical']}")
        emit("crash_resume/daemon_restart", restart["cold_batch_s"] * 1e6,
             f"identical={restart['restart_identical']};"
             f"warm_p50_ms={restart['warm_p50_ms']:.2f};"
             f"sigterm_clean={restart['sigterm_clean']}")

        # intra-run, hardware-portable: how much faster a fully-resumed
        # pricing pass is than pricing cold (the point of the journal)
        resume_speedup = ref_s / max(storm["resumed_price_s"], 1e-9)
        payload = {
            **storm, **torn, **restart,
            "n_cells": len(requests),
            "reference_s": ref_s,
            "resume_speedup": resume_speedup,
        }
        bench_json("crash_resume", payload)

        problems = [k for k in (
            "storm_all_sigkilled", "storm_identical", "resumed_all",
            "repriced_ok", "torn_detected", "torn_tail_quarantined",
            "torn_kept_committed_prefix", "torn_reprice_identical",
            "torn_journal_healed", "restart_pidfile_ok",
            "restart_identical", "restart_memo_restored",
            "restart_answered_warm", "restart_client_rode_window",
            "restart_warm_p50_ok", "sigterm_clean") if not payload[k]]
        if problems:
            raise AssertionError(
                f"crash-resume soak violated the durability model: "
                f"gates={problems} "
                f"repriced_fraction={payload['repriced_fraction']:.2f}")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main():
    if "jax" in sys.modules:
        env = host_env()
        env.pop(faults.ENV_VAR, None)
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_crash_resume"], env=env)
        if proc.returncode != 0:
            raise RuntimeError(
                f"re-exec'd crash-resume soak failed "
                f"(exit {proc.returncode})")
        return
    _main_impl()


if __name__ == "__main__":
    if len(sys.argv) == 4 and sys.argv[1] == "--child":
        _child_main(sys.argv[2], sys.argv[3])
    else:
        main()
