"""Self-healing worker pool: crash/hang recovery, poison-task quarantine.

The crash/hang scenarios run in fresh subprocesses: a bare interpreter
(no jax loaded) gets the fork start method, so an in-process
``faults.install`` reaches pool workers by memory inheritance and the
scenario is deterministic regardless of what the surrounding pytest
session has imported.  The subprocess prints a JSON verdict; the test
asserts on it.

Also covers the pure in-process pieces: ``default_workers`` fallback
order (affinity OSError, ``REPRO_MAX_WORKERS`` as a cap not an
override), ``guarded_batch`` exceptions-as-values, the serial path's
immunity to worker-site faults, and deadline-env parsing.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro import faults
from repro.core.engine.pool import (
    PoisonTaskError,
    TaskPool,
    _default_deadline,
    default_workers,
    guarded_batch,
)

# repro is a namespace package (__file__ is None); anchor on a real module
SRC = str(Path(faults.__file__).resolve().parents[1])


def _run_scenario(script: str, *argv: str) -> dict:
    """Run a chaos scenario in a clean interpreter; return its JSON verdict."""
    env = dict(os.environ, PYTHONPATH=SRC)
    # the CI chaos job exports a plan/deadline; scenarios install their own
    env.pop(faults.ENV_VAR, None)
    env.pop("REPRO_POOL_DEADLINE_S", None)
    proc = subprocess.run([sys.executable, "-c", script, *argv],
                          capture_output=True, text=True, timeout=120,
                          env=env)
    assert proc.returncode == 0, (
        f"scenario exited {proc.returncode}\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


_CRASH_RECOVERY = """
import json, os, sys
from repro import faults
from repro.core.engine.pool import TaskPool, guarded_batch

def f(x):
    return x * x + 1

token_dir = sys.argv[1]
faults.install(faults.FaultPlan(seed=3, token_dir=token_dir, faults={
    "pool.worker_crash": faults.FaultSpec(at=(0,), max_fires=1, token=True)}))
calls = [(f, (i,)) for i in range(40)]
with TaskPool(parallel=True, max_workers=2, backoff_base_s=0.001) as pool:
    outcomes = pool.run(calls)
faults.clear()
print(json.dumps({
    "identical": outcomes == guarded_batch(calls),
    "health": pool.health,
    "tokens": sorted(os.listdir(token_dir)),
}))
"""


def test_worker_crash_recovers_bitwise(tmp_path):
    verdict = _run_scenario(_CRASH_RECOVERY, str(tmp_path))
    assert verdict["identical"], "recovered outcomes differ from fault-free"
    assert verdict["health"]["rebuilds"] >= 1
    assert verdict["health"]["broken_pools"] >= 1
    assert verdict["health"]["quarantined"] == 0
    # exactly one global crash, proven by exactly one claimed token
    assert verdict["tokens"] == ["pool_worker_crash.0.token"]


_HANG_RECOVERY = """
import json, os, sys
from repro import faults
from repro.core.engine.pool import TaskPool, guarded_batch

def f(x):
    return 3 * x - 7

token_dir = sys.argv[1]
faults.install(faults.FaultPlan(seed=4, token_dir=token_dir, faults={
    "pool.worker_hang": faults.FaultSpec(at=(0,), max_fires=1, arg=30.0,
                                         token=True)}))
calls = [(f, (i,)) for i in range(24)]
with TaskPool(parallel=True, max_workers=2, chunk_deadline_s=1.0,
              backoff_base_s=0.001) as pool:
    outcomes = pool.run(calls)
faults.clear()
print(json.dumps({
    "identical": outcomes == guarded_batch(calls),
    "health": pool.health,
    "tokens": sorted(os.listdir(token_dir)),
}))
"""


def test_hung_worker_reaped_within_deadline(tmp_path):
    verdict = _run_scenario(_HANG_RECOVERY, str(tmp_path))
    assert verdict["identical"]
    assert verdict["health"]["hung_chunks"] >= 1
    assert verdict["health"]["rebuilds"] >= 1
    assert verdict["health"]["quarantined"] == 0
    assert verdict["tokens"] == ["pool_worker_hang.0.token"]


_POISON_QUARANTINE = """
import json
from repro import faults
from repro.core.engine.pool import PoisonTaskError, TaskPool

def f(x):
    return x + 1

# rate=1.0, no token: every chunk of every (rebuilt) worker crashes, so the
# retry budget exhausts, splits to singles, exhausts again -> quarantine
faults.install(faults.FaultPlan(seed=5, faults={
    "pool.worker_crash": faults.FaultSpec(rate=1.0)}))
calls = [(f, (i,)) for i in range(4)]
with TaskPool(parallel=True, max_workers=2, max_retries=1,
              backoff_base_s=0.001) as pool:
    outcomes = pool.run(calls)
faults.clear()
print(json.dumps({
    "all_poisoned": all(kind == "err" and type(exc).__name__ ==
                        "PoisonTaskError" for kind, exc in outcomes),
    "count": len(outcomes),
    "health": pool.health,
}))
"""


def test_poison_tasks_quarantined_parent_survives():
    verdict = _run_scenario(_POISON_QUARANTINE)
    assert verdict["all_poisoned"]
    assert verdict["count"] == 4
    assert verdict["health"]["quarantined"] == 4
    assert verdict["health"]["rebuilds"] >= 2


# prepended to a scenario: the parent sleeps after each chunk it submits,
# so a worker killed by its first chunk breaks the executor while chunks
# are still being submitted (the race a loaded host produces)
_SLOW_SUBMIT = """
import time
from concurrent.futures import ProcessPoolExecutor
_submit = ProcessPoolExecutor.submit
def _slow_submit(self, *args, **kwargs):
    future = _submit(self, *args, **kwargs)
    time.sleep(0.3)
    return future
ProcessPoolExecutor.submit = _slow_submit
"""


def test_worker_death_during_submission_crash_recovers(tmp_path):
    verdict = _run_scenario(_SLOW_SUBMIT + _CRASH_RECOVERY, str(tmp_path))
    assert verdict["identical"], "recovered outcomes differ from fault-free"
    assert verdict["health"]["rebuilds"] >= 1
    assert verdict["health"]["quarantined"] == 0
    assert verdict["tokens"] == ["pool_worker_crash.0.token"]


def test_worker_death_during_submission_poison_quarantined():
    verdict = _run_scenario(_SLOW_SUBMIT + _POISON_QUARANTINE)
    assert verdict["all_poisoned"]
    assert verdict["health"]["quarantined"] == 4


_ENGINE_RECOVERY = """
import json, sys
from repro import faults
from repro.core.engine import Explorer, Workload
from repro.core.machines import GPUMachine
from repro.core.specs import star_stencil_3d

SMALL = GPUMachine(name="A100/8", n_sms=13, clock_hz=1.41e9,
                   l1_bytes=192 * 1024, l2_bytes=20 * 1024 * 1024 // 8,
                   dram_bw=1400e9 / 8, l2_bw=5000e9 / 8,
                   peak_flops_dp=9.7e12 / 8)
wl = [Workload("stencil", gpu_spec=star_stencil_3d(r=1, domain=(16, 24, 32)))]

serial = Explorer(parallel=False).explore(wl, [SMALL])
faults.install(faults.FaultPlan(seed=6, token_dir=sys.argv[1], faults={
    "pool.worker_crash": faults.FaultSpec(at=(0,), max_fires=1, token=True)}))
chaotic = Explorer(parallel=True, max_workers=2).explore(wl, [SMALL])
faults.clear()

def key(report):
    return [(e.workload, e.machine, e.index, e.perf, e.limiter)
            for e in report.entries]

print(json.dumps({
    "identical": key(serial) == key(chaotic),
    "entries": len(chaotic.entries),
    "skipped": [s.reason for s in chaotic.skipped],
    "pool_health": chaotic.cache_stats.get("pool_health", {}),
}))
"""


def test_engine_sweep_identical_across_worker_crash(tmp_path):
    """The acceptance criterion end-to-end: a sweep whose pool loses a
    worker mid-flight reproduces the exhaustive ranking exactly, and the
    report carries the recovery in ``cache_stats["pool_health"]``."""
    verdict = _run_scenario(_ENGINE_RECOVERY, str(tmp_path))
    assert verdict["identical"], f"ranking diverged: {verdict}"
    assert verdict["entries"] > 0
    assert not any("quarantined" in r for r in verdict["skipped"])
    assert verdict["pool_health"].get("rebuilds", 0) >= 1


# ---- in-process pieces ----------------------------------------------------

def _double(x):
    return x * 2


def _boom(x):
    raise ValueError(f"bad input {x}")


def test_guarded_batch_returns_exceptions_as_values():
    out = guarded_batch([(_double, (21,)), (_boom, (3,)), (_double, (0,))])
    assert out[0] == ("ok", 42)
    kind, exc = out[1]
    assert kind == "err" and isinstance(exc, ValueError)
    assert "bad input 3" in str(exc)
    assert out[2] == ("ok", 0)


def test_serial_path_immune_to_worker_sites():
    """Crash/hang sites live only in the worker entry point: with a
    kill-everything plan installed, the serial path must still run."""
    with faults.injected(faults.FaultPlan(seed=1, faults={
            "pool.worker_crash": faults.FaultSpec(rate=1.0),
            "pool.worker_hang": faults.FaultSpec(rate=1.0, arg=60.0)})):
        pool = TaskPool(parallel=False)
        assert pool.run([(_double, (4,))]) == [("ok", 8)]
        assert pool.health["quarantined"] == 0


def test_default_workers_affinity_oserror_falls_back(monkeypatch):
    def broken_affinity(pid):
        raise OSError("affinity unavailable")

    monkeypatch.delattr(os, "process_cpu_count", raising=False)
    monkeypatch.setattr(os, "sched_getaffinity", broken_affinity,
                        raising=False)
    monkeypatch.setattr(os, "cpu_count", lambda: 5)
    monkeypatch.delenv("REPRO_MAX_WORKERS", raising=False)
    assert default_workers() == 5


def test_default_workers_env_is_cap_not_override(monkeypatch):
    monkeypatch.delattr(os, "process_cpu_count", raising=False)
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(5)),
                        raising=False)
    monkeypatch.setenv("REPRO_MAX_WORKERS", "2")
    assert default_workers() == 2          # caps below available
    monkeypatch.setenv("REPRO_MAX_WORKERS", "64")
    assert default_workers() == 5          # never raises above available
    monkeypatch.setenv("REPRO_MAX_WORKERS", "not-a-number")
    assert default_workers() == 5
    monkeypatch.setenv("REPRO_MAX_WORKERS", "-3")
    assert default_workers() == 5


def test_cpu_count_none_yields_one_worker(monkeypatch):
    monkeypatch.delattr(os, "process_cpu_count", raising=False)
    monkeypatch.delattr(os, "sched_getaffinity", raising=False)
    monkeypatch.setattr(os, "cpu_count", lambda: None)
    monkeypatch.delenv("REPRO_MAX_WORKERS", raising=False)
    assert default_workers() == 1


def test_pool_deadline_env_parsing(monkeypatch):
    monkeypatch.delenv("REPRO_POOL_DEADLINE_S", raising=False)
    assert _default_deadline() is None
    monkeypatch.setenv("REPRO_POOL_DEADLINE_S", "2.5")
    assert _default_deadline() == 2.5
    assert TaskPool().chunk_deadline_s == 2.5
    assert TaskPool(chunk_deadline_s=7.0).chunk_deadline_s == 7.0
    monkeypatch.setenv("REPRO_POOL_DEADLINE_S", "0")
    assert _default_deadline() is None
    monkeypatch.setenv("REPRO_POOL_DEADLINE_S", "garbage")
    assert _default_deadline() is None


def test_poison_error_is_runtime_error():
    """The engine's outcome reader treats RuntimeError as skippable; the
    quarantine record must ride that path, not abort sweeps."""
    assert issubclass(PoisonTaskError, RuntimeError)
    with pytest.raises(RuntimeError):
        raise PoisonTaskError("x")
