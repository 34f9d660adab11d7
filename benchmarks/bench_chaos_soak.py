"""Chaos soak: the failure-model gate (DESIGN.md §13).

Drives the whole pricing stack through a deterministic fault plan and
checks the robustness invariant end to end: **under any fault plan, every
request either completes bitwise-identically to the fault-free run or is
explicitly flagged degraded/rejected — never wrong, never hung.**

Four phases, each emitting deterministic boolean gates into
``BENCH_chaos_soak.json`` (checked by ``scripts/check_bench.py``):

  A. **fault-free reference** — each distinct request priced serially;
     the rankings are the ground truth every later phase compares against.
  B. **cache damage** — the persisted invariant cache is corrupted on
     disk; the reload must quarantine it (``<path>.corrupt``, health
     counter), re-price bitwise-identically cold, and rebuild a clean
     reloadable blob.
  C. **chaos daemon soak** — a live daemon (parallel engine, warm cache)
     under a plan that kills one pool worker, wedges another past the
     chunk deadline, corrupts the cache load, and drops a client socket
     mid-response — while retrying storm clients, an abandoning client,
     and a zero-deadline probe hammer it.  The daemon must stay alive,
     every completed result must match phase A or carry
     ``degraded=True``, the scheduler counter identity must hold, and
     the token files must prove the worker faults actually fired.
  D. **pool recovery** — an engine-level sweep that loses a worker
     mid-flight must reproduce the exhaustive serial ranking exactly.

Worker-side faults propagate by fork inheritance, so the bench re-execs
itself into a clean interpreter if jax is already loaded (jax forces the
forkserver start method, whose workers cannot see an in-process plan).
"""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

from repro import faults
from repro.api import gpu_request, price
from repro.core.engine import Explorer
from repro.core.engine.pool import host_env
from repro.core.specs import star_stencil_3d
from repro.serve import PriceClient, PricingDaemon
from repro.serve.daemon import can_bind_unix_sockets
from repro.serve.schema import encode

from .common import SMALL_A100, bench_json, configs_512, emit

STORM_CLIENTS = 3
POOL_DEADLINE_S = "2.0"     # reaps the injected 30 s hang

DOMAINS = [(16, 24, 32), (24, 24, 32), (16, 32, 32),
           (24, 32, 32), (16, 24, 48), (24, 32, 48)]


def distinct_requests():
    configs = configs_512()[:6]
    return [gpu_request(star_stencil_3d(r=1, domain=d), SMALL_A100, configs)
            for d in DOMAINS]


def ranking_key(result):
    """Bitwise ranking fingerprint (perf floats survive the JSON wire
    exactly, so wire results compare against in-process references)."""
    return [(e.workload, e.machine, e.index, e.perf, e.limiter)
            for e in result.entries]


def _flip_byte(path, offset=-3):
    blob = bytearray(open(path, "rb").read())
    blob[offset] ^= 0x40
    with open(path, "wb") as f:
        f.write(bytes(blob))


# ------------------------------------------------------------------------
# phase B: on-disk cache damage -> quarantine -> bitwise rebuild
# ------------------------------------------------------------------------
def phase_cache_damage(tmp, requests, references):
    cache_path = os.path.join(tmp, "damage.invcache")
    warm = Explorer(parallel=False, cache_path=cache_path)
    req = requests[0]
    assert ranking_key(price(req, engine=warm)) == references[0]
    warm.save_cache()

    _flip_byte(cache_path)
    healed = Explorer(parallel=False, cache_path=cache_path)
    quarantined = (
        healed.cache.health["corrupt_quarantined"] == 1
        and os.path.exists(cache_path + ".corrupt")
        and healed.cache.loaded_entries == 0)
    identical_cold = ranking_key(price(req, engine=healed)) == references[0]
    healed.save_cache()
    rebuilt = Explorer(parallel=False,
                       cache_path=cache_path).cache.loaded_entries > 0
    return {"cache_quarantined": quarantined,
            "cache_reprice_identical": identical_cold,
            "cache_rebuilt": rebuilt}


# ------------------------------------------------------------------------
# phase C: chaos daemon soak
# ------------------------------------------------------------------------
def phase_chaos_daemon(tmp, requests, references):
    sock = os.path.join(tmp, "chaos.sock")
    cache_path = os.path.join(tmp, "chaos.invcache")
    token_dir = os.path.join(tmp, "tokens")

    # prime a persistent cache so the injected load-corruption has a real
    # blob to damage
    primer = Explorer(parallel=False, cache_path=cache_path)
    price(requests[0], engine=primer)
    primer.save_cache()

    plan = faults.FaultPlan(seed=2026, token_dir=token_dir, faults={
        "pool.worker_crash": faults.FaultSpec(at=(0,), max_fires=1,
                                              token=True),
        "pool.worker_hang": faults.FaultSpec(at=(1,), max_fires=1,
                                             arg=30.0, token=True),
        "invcache.load": faults.FaultSpec(at=(0,)),
        "serve.socket_drop": faults.FaultSpec(at=(2,), max_fires=1),
    })
    os.environ["REPRO_POOL_DEADLINE_S"] = POOL_DEADLINE_S
    faults.install(plan)
    mismatches, failures = [], []
    n_results = n_degraded = 0
    pool_health: dict = {}
    try:
        engine = Explorer(parallel=True, max_workers=2,
                          cache_path=cache_path)
        load_quarantined = \
            engine.cache.health["corrupt_quarantined"] == 1
        with PricingDaemon(sock, engine=engine) as daemon:
            results_lock = threading.Lock()
            collected: list = []

            def storm(idx):
                try:
                    with PriceClient(sock, retries=5, backoff_s=0.02,
                                     timeout=300) as client:
                        out = client.price_many(requests)
                    with results_lock:
                        collected.append((idx, out))
                except BaseException as exc:  # noqa: BLE001 — gated below
                    failures.append(f"storm[{idx}]: {exc!r}")

            threads = [threading.Thread(target=storm, args=(i,))
                       for i in range(STORM_CLIENTS)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            hung_requests = sum(t.is_alive() for t in threads)

            # abandoning client: submits one request, never reads the answer
            quitter = PriceClient(sock)
            quitter._send({"op": "price", "id": 1, "request": encode(
                gpu_request(star_stencil_3d(r=2, domain=(20, 28, 36)),
                            SMALL_A100, configs_512()[:6]))})
            time.sleep(0.05)
            quitter.close()

            # zero-deadline probe on a fresh digest (a memoized one would
            # answer exactly): must degrade explicitly, never block
            probe_req = gpu_request(
                star_stencil_3d(r=2, domain=(16, 24, 40)),
                SMALL_A100, configs_512()[:6])
            with PriceClient(sock, retries=5, backoff_s=0.02,
                             timeout=300) as probe:
                degraded_result = probe.price(probe_req, deadline_s=0.0)
                daemon_alive = probe.ping()
                stats = probe.stats()
            storm_s = time.perf_counter() - t0

            for idx, out in collected:
                for i, result in enumerate(out):
                    n_results += 1
                    if result.degraded:
                        n_degraded += 1
                        continue
                    if ranking_key(result) != references[i]:
                        mismatches.append(f"storm[{idx}] request {i}")
                    # pool health counters are cumulative across sweeps of
                    # the shared engine pool: keep the latest (max) snapshot
                    for k, v in (result.cache_stats.get("pool_health")
                                 or {}).items():
                        pool_health[k] = max(pool_health.get(k, 0), v)
                    quarantine_skips = [
                        s for s in result.skipped
                        if "quarantined" in str(s.reason)]
                    if quarantine_skips:
                        mismatches.append(
                            f"storm[{idx}] request {i}: "
                            f"{len(quarantine_skips)} quarantined configs")
        fault_stats = faults.stats()
    finally:
        faults.clear()
        os.environ.pop("REPRO_POOL_DEADLINE_S", None)

    tokens = sorted(os.listdir(token_dir)) if os.path.isdir(token_dir) \
        else []
    c = stats
    counters_consistent = (
        c["requests"] == (c["memo_hits"] + c["dedupe_joins"]
                          + c["keys_priced"] + c["cancelled"])
        and c["errors"] == 0)
    return {
        "daemon_alive": bool(daemon_alive),
        "all_match_or_degraded": not mismatches and not failures,
        "mismatches": mismatches,
        "client_failures": failures,
        "hung_requests": hung_requests,
        "n_results": n_results,
        "n_degraded_storm": n_degraded,
        "deadline_degraded": bool(degraded_result.degraded
                                  and degraded_result.entries),
        "counters_consistent": counters_consistent,
        "counters": {k: c[k] for k in
                     ("requests", "memo_hits", "dedupe_joins", "keys_priced",
                      "cancelled", "rejected", "degraded", "errors")},
        "load_quarantined": load_quarantined,
        "crash_token_claimed": "pool_worker_crash.0.token" in tokens,
        "hang_token_claimed": "pool_worker_hang.0.token" in tokens,
        "socket_drop_fired":
            fault_stats.get("serve.socket_drop", {}).get("fired", 0) >= 1,
        "pool_health": pool_health,
        "storm_s": storm_s,
    }


# ------------------------------------------------------------------------
# phase D: engine-level worker-crash recovery, bitwise vs serial
# ------------------------------------------------------------------------
def phase_pool_recovery(tmp):
    token_dir = os.path.join(tmp, "tokens-pool")
    req = gpu_request(star_stencil_3d(r=2, domain=(24, 32, 48)),
                      SMALL_A100, configs_512())
    serial = price(req, engine=Explorer(parallel=False))
    faults.install(faults.FaultPlan(seed=7, token_dir=token_dir, faults={
        "pool.worker_crash": faults.FaultSpec(at=(0,), max_fires=1,
                                              token=True)}))
    try:
        chaotic = price(req, engine=Explorer(parallel=True, max_workers=2))
    finally:
        faults.clear()
    health = chaotic.cache_stats.get("pool_health", {})
    return {
        "pool_recovery_identical":
            ranking_key(chaotic) == ranking_key(serial),
        "pool_rebuilds": health.get("rebuilds", 0),
        "pool_quarantined": health.get("quarantined", 0),
        "n_entries": len(chaotic.entries),
    }


def _main_impl():
    tmp = tempfile.mkdtemp(prefix="bench-chaos-")
    try:
        if not can_bind_unix_sockets(tmp):
            raise RuntimeError("environment cannot bind Unix sockets; "
                               "chaos soak needs a real socket")
        # isolate from any ambient CI fault plan: this bench owns its plans
        os.environ.pop(faults.ENV_VAR, None)
        os.environ.pop("REPRO_POOL_DEADLINE_S", None)
        faults.clear()

        requests = distinct_requests()
        t0 = time.perf_counter()
        references = [ranking_key(price(r)) for r in requests]
        ref_s = time.perf_counter() - t0

        cache = phase_cache_damage(tmp, requests, references)
        chaos = phase_chaos_daemon(tmp, requests, references)
        pool = phase_pool_recovery(tmp)

        emit("chaos_soak/reference", ref_s * 1e6,
             f"distinct={len(requests)}")
        emit("chaos_soak/cache_damage", 0.0,
             f"quarantined={cache['cache_quarantined']};"
             f"identical={cache['cache_reprice_identical']};"
             f"rebuilt={cache['cache_rebuilt']}")
        emit("chaos_soak/daemon", chaos["storm_s"] * 1e6,
             f"alive={chaos['daemon_alive']};"
             f"results={chaos['n_results']};"
             f"match_or_degraded={chaos['all_match_or_degraded']};"
             f"hung={chaos['hung_requests']};"
             f"pool_health={chaos['pool_health']}")
        emit("chaos_soak/pool_recovery", 0.0,
             f"identical={pool['pool_recovery_identical']};"
             f"rebuilds={pool['pool_rebuilds']}")

        faults_exercised = (
            chaos["crash_token_claimed"] and chaos["hang_token_claimed"]
            and chaos["socket_drop_fired"] and chaos["load_quarantined"])
        payload = {
            **cache,
            "daemon_alive": chaos["daemon_alive"],
            "all_match_or_degraded": chaos["all_match_or_degraded"],
            "hung_requests": chaos["hung_requests"],
            "n_results": chaos["n_results"],
            "deadline_degraded": chaos["deadline_degraded"],
            "counters_consistent": chaos["counters_consistent"],
            "counters": chaos["counters"],
            "faults_exercised": faults_exercised,
            "pool_recovery_identical": pool["pool_recovery_identical"],
            "pool_recovery_rebuilds": pool["pool_rebuilds"],
            "quarantined_tasks": pool["pool_quarantined"],
            "storm_s": chaos["storm_s"],
            "reference_s": ref_s,
        }
        bench_json("chaos_soak", payload)

        problems = [k for k in (
            "cache_quarantined", "cache_reprice_identical", "cache_rebuilt",
            "daemon_alive", "all_match_or_degraded", "deadline_degraded",
            "counters_consistent", "faults_exercised",
            "pool_recovery_identical") if not payload[k]]
        if problems or payload["hung_requests"] or payload["quarantined_tasks"]:
            raise AssertionError(
                f"chaos soak violated the failure model: gates={problems} "
                f"hung={payload['hung_requests']} "
                f"quarantined={payload['quarantined_tasks']} "
                f"mismatches={chaos['mismatches']} "
                f"failures={chaos['client_failures']}")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main():
    if "jax" in sys.modules:
        # jax forces the forkserver pool start method, whose workers cannot
        # inherit this process's in-memory fault plan — re-exec the bench in
        # a clean interpreter where plain fork is available
        env = host_env()
        env.pop(faults.ENV_VAR, None)
        env.pop("REPRO_POOL_DEADLINE_S", None)
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_chaos_soak"], env=env)
        if proc.returncode != 0:
            raise RuntimeError(
                f"re-exec'd chaos soak failed (exit {proc.returncode})")
        return
    _main_impl()


if __name__ == "__main__":
    main()
