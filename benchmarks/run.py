"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (see DESIGN.md §6 for the
figure-to-module index).  ``python -m benchmarks.run [module ...]`` runs a
subset.

Set ``REPRO_TRACE_DIR=<dir>`` to capture one Perfetto-loadable Chrome
trace per module (``<dir>/<module>.trace.json``, DESIGN.md §14): telemetry
is enabled for the whole run and the span buffer is dumped and reset
between modules, so each trace shows exactly that benchmark's pipeline.

JAX's persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``<checkout>/.jax-cache``; it is set through the environment,
so this process does not import JAX before a module does.  Modules that start pricing or
tracing children pin them to the CPU (``JAX_PLATFORMS=cpu``), so only this
process ever holds an accelerator.
"""
from __future__ import annotations

import os
import sys
import time
import traceback

MODULES = [
    "bench_l1_cycles",        # fig 12
    "bench_l2_volume",        # figs 13/14/15
    "bench_dram_volume",      # figs 19-22
    "bench_cachesim_core",    # DESIGN §10 vectorized simulator vs oracle
    "bench_capacity_fit",     # figs 16/17/18
    "bench_layer_condition",  # fig 23 / §5.7
    "bench_perf_ranking",     # figs 24/25 / §5.8
    "bench_kernel_select",    # fig 1 workflow on TPU
    "bench_machine_compare",  # §1.1 cross-machine/hypothetical-GPU exploration
    "bench_model_suite",      # DESIGN §8 model zoo -> kernel plans, one sweep
    "bench_pruned_search",    # §5 tiered bound-then-refine + persistent cache
    "bench_design_space",     # DESIGN §11 geometry-factored machine-axis sweep
    "bench_trace_extract",    # DESIGN §9 spec-extraction frontend parity/cost
    "bench_serve_soak",       # DESIGN §12 daemon warm latency + dedupe
    "bench_chaos_soak",       # DESIGN §13 failure model under fault injection
    "bench_crash_resume",     # DESIGN §15 durability: kill/resume/restart
    "bench_roofline",         # §Roofline table (reads experiments/dryrun)
]


def _dump_trace(trace_dir: str | None, name: str) -> None:
    if not trace_dir:
        return
    from repro import obs

    if obs.spans():
        path = os.path.join(trace_dir, f"{name}.trace.json")
        print(f"# wrote {obs.write_trace(path)}", flush=True)
    obs.reset()
    obs.enable()        # a bench may have toggled telemetry; re-arm


def main() -> None:
    import importlib

    from repro.kernels import use_compile_cache

    use_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    trace_dir = os.environ.get("REPRO_TRACE_DIR")
    if trace_dir:
        from repro import obs

        os.makedirs(trace_dir, exist_ok=True)
        obs.enable()
    selected = sys.argv[1:] or MODULES
    failures = []
    for name in selected:
        t0 = time.time()
        print(f"# ==== {name} ====", flush=True)
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            mod.main()
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            failures.append(name)
            print(f"# {name} FAILED:\n{traceback.format_exc()}", flush=True)
        finally:
            _dump_trace(trace_dir, name)
    if failures:
        print(f"# FAILURES: {failures}")
        sys.exit(1)
    print("# all benchmarks completed")


if __name__ == "__main__":
    main()
