"""Fixtures of the benchmark's own tests: they run on the CPU, at tiny
sizes, with the harness's look for a chip skipped."""
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.harness import cell  # noqa: E402

BENCH = cell.benchmark(ROOT)
V5E = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def cells(mode: str | None = None) -> list:
    """Names of BENCHMARK.json's cells, those of traffic ``mode`` where
    given."""
    return [w["name"] for w in BENCH["workloads"]
            if mode is None
            or cell.resolve(BENCH, w["name"])["traffic"]["mode"] == mode]


def tiny_config(config: dict) -> dict:
    """A configuration cut to its family's tiny size."""
    return cell.family(config)[0].tiny(config)


def tiny_cell(name: str) -> dict:
    """The cell ``name`` of BENCHMARK.json at its family's tiny size, and
    a cold-pricing mix on the family's tiny grid."""
    c = cell.resolve(BENCH, name)
    c["config"] = tiny_config(c["config"])
    if "axes" in c["traffic"]:
        c["traffic"]["axes"] = cell.family(c["config"])[0].TINY_AXES
    return c


@pytest.fixture
def cpu_harness(monkeypatch):
    """Run cells on the CPU: peaks and machine of a v5e, kernels in
    interpret mode (so not Mosaic custom calls), files under the repo's
    ignored ``.bench-out``.  A CPU trace has no TPU planes, so a sweep's
    device time per call reads a fixed 1 ms."""
    from bench.harness import runner, sweep

    monkeypatch.chdir(ROOT)
    monkeypatch.setattr(runner.device, "peaks_for", lambda kind: V5E)
    monkeypatch.setattr(runner.Run, "machine_name", lambda self: "TPUv5e")
    monkeypatch.setattr(runner.device, "check_compiled", lambda *a: None)
    monkeypatch.setattr(sweep, "_device_per_call", lambda *a: 1e-3)

    def run(name, seed=7, seconds=0.5, trace=False, control=False):
        import jax

        return runner.run_cell(tiny_cell(name), seed=seed, seconds=seconds,
                               trace=trace, devices=jax.devices(),
                               t0=time.perf_counter(), control=control)

    return run
