"""One run of one cell: the parts every traffic mode shares.

A run starts the pricing daemon, hands itself to the cell's traffic mode
(which sets up, measures its window and checks what the window produced),
reduces the profiler trace where one was taken, reads the cell's metrics
through their readers, and prints the result.  Earlier output lines start
with ``#``; the last line of standard output is the result, and the last
lines of standard error are the numbers compared, each beside its limit.

With ``control`` set, the family's control (its plain reference in the
next lower precision) takes the program's place on the timed path, so the
same run reads the numbers that set a limit's upper end.
"""
from __future__ import annotations

import functools
import json
import math
import os
import shutil
import sys
import time

from . import cell as cells
from . import daemon, device, trace as tr

# programs lowered and compiled by XLA since the process started
# (``jax.monitoring`` events; a cache load is lowered but not compiled)
COMPILES = {"lowered": 0, "compiled": 0}
_LISTENING = False


def _listen_for_compiles() -> None:
    global _LISTENING
    if _LISTENING:
        return
    import jax

    def on_event(name, _secs, **_kw):
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            COMPILES["lowered"] += 1
        elif name == "/jax/core/compile/backend_compile_duration":
            COMPILES["compiled"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    _LISTENING = True


def seed_key(seed: int):
    """A PRNG key from any whole seed: all 64 bits of it count."""
    import jax

    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _abs_error(a, b):
    import jax.numpy as jnp

    d = jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))
    return jnp.where(jnp.isnan(d), jnp.inf, d).max()


@functools.cache
def _abs_error_jit():
    import jax

    return jax.jit(_abs_error)


def max_abs_error(out, ref) -> float:
    """Largest |out - ref| over all elements, in float32, on the device;
    NaN anywhere, or a shape that differs, reads as infinity."""
    if out.shape != ref.shape:
        return math.inf
    return float(_abs_error_jit()(out, ref))


class Run:
    """State of one run; the traffic mode fills ``record``."""

    def __init__(self, cell: dict, *, seed: int, seconds: float,
                 trace: bool, devices, t0: float, control: bool = False,
                 root: str = cells.ROOT):
        self.cell = cell
        self.config = cell["config"]
        self.traffic = cell["traffic"]
        self.program, self.ref, self.work = cells.family(self.config)
        self.shape = self.program.shape(self.config)
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.control = control
        self.devices = devices
        self.t0 = t0
        self.root = root
        self.out_dir = os.path.join(".bench-out", cell["name"])
        self.peaks = device.peaks_for(devices[0].device_kind)
        self.checks: list = []      # (name, value, limit)
        self.attempted = 0
        self.failed = 0
        self.record: dict = {"trace": None}
        self.daemon = None          # (process, socket, log) until closed
        self.client = None
        self.phases: list = []      # (set-up phase, seconds)
        self._mark = t0

    def phase(self, name: str) -> None:
        """Close the set-up phase ``name`` at this instant."""
        now = time.perf_counter()
        self.phases.append((name, now - self._mark))
        self._mark = now

    def end_setup(self) -> None:
        self.record["setup_s"] = time.perf_counter() - self.t0
        self.log("setup: " + ", ".join(f"{n} {s!r} s" for n, s in self.phases))

    # ---- output ----------------------------------------------------------
    @staticmethod
    def log(text: str) -> None:
        print("# " + text, flush=True)

    def check(self, name: str, value: float, limit: float) -> bool:
        """Record one number compared with its limit (value <= limit)."""
        self.checks.append((name, value, limit))
        return value <= limit

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(v <= lim for _, v, lim in self.checks)

    def entry(self, shape: dict, cfg: dict):
        """The function the timed path compiles for ``cfg``: the program's
        entry point, or, in a control run, the control under its name."""
        fn = self.program.entry(shape, cfg)
        if not self.control:
            return fn
        ref = self.ref

        def control(*args):
            return ref.control(shape, args).astype(args[0].dtype)

        control.__name__ = control.__qualname__ = fn.__name__
        return control

    def compile(self, shape: dict, cfg: dict, args: tuple):
        """The timed path's program for ``cfg``, compiled for ``args`` and
        checked to be a Mosaic kernel (a control run's is plain XLA, so
        it is not checked)."""
        import jax

        fn = jax.jit(self.entry(shape, cfg)).lower(*args).compile()
        if not self.control:
            device.check_compiled(fn, f"{self.program.label(shape)} "
                                      f"{self.program.slug(cfg)}")
        return fn

    # ---- pricing through the daemon -------------------------------------
    def machine_name(self) -> str:
        from repro.core.machines import machine_for_device

        return machine_for_device(self.devices[0].device_kind).name

    def price(self, cands: tuple, label: str) -> tuple:
        """(ranking as [(config, predicted s)], skipped configs) served by
        the daemon for the traced candidates ``cands``."""
        from repro.api import pallas_request

        if self.client is None:
            from repro.serve import PriceClient

            daemon.ready(self.root, *self.daemon)
            self.client = PriceClient(self.daemon[1])
        machine = self.machine_name()
        result = self.client.price(pallas_request(cands, machine,
                                                  workload=label))
        ranking = [(e.config, e.estimate.total_time)
                   for e in result.ranking(label, machine)]
        skipped = [s.config for s in result.skipped]
        return ranking, skipped

    def ranking_faults(self, shape: dict, cands: tuple, ranking: list,
                       skipped: list) -> dict:
        """What is wrong with one served ranking, counted three ways:
        configurations that are not the traced ones exactly once
        (``set``), predicted times that are not finite, positive and in
        ascending order (``order``), and predictions faster than the
        chip's peaks allow for the work the shape needs (``floor``)."""
        asked = sorted(json.dumps(c, sort_keys=True) for c, _ in cands)
        got = sorted(json.dumps(c, sort_keys=True)
                     for c in [c for c, _ in ranking] + list(skipped))
        times = [t for _, t in ranking]
        order = sum(1 for t in times if not (math.isfinite(t) and t > 0))
        order += sum(1 for a, b in zip(times, times[1:]) if b < a)
        need = self.work.work(shape)
        least = max(need["flops"] / self.peaks["flops_bf16"],
                    need["bytes"] / self.peaks["hbm_bytes_per_s"])
        floor = sum(1 for t in times if t < least)
        return {"set": int(asked != got) + int(not ranking), "order": order,
                "floor": floor}

    def log_ranking(self, label: str, ranking: list, skipped: list) -> None:
        slug = self.program.slug
        self.log(f"ranking {label}: " + ", ".join(
            f"{slug(c)}={t * 1e3!r}ms" for c, t in ranking))
        if skipped:
            self.log(f"skipped by the estimator {label}: "
                     + ", ".join(slug(c) for c in skipped))

    # ---- tracing -----------------------------------------------------------
    def trace_dir(self) -> str:
        return os.path.join(self.out_dir, "trace")

    def start_trace(self, always: bool = False) -> None:
        """Start the profiler for the window: in a ``--trace 1`` run, or
        in any run of a mode whose end-to-end metrics come from it."""
        if self.trace or always:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir(), profiler_options=opts)

    def stop_trace(self, always: bool = False) -> None:
        if self.trace or always:
            import jax

            jax.profiler.stop_trace()
            t = tr.load(tr.find_xplane(self.trace_dir()))
            lo, hi = tr.span_bounds(t, "bench.window")
            self.record["trace"] = {"trace": t, "lo": lo, "hi": hi}


def run_cell(cell: dict, *, seed: int, seconds: float, trace: bool,
             devices, t0: float, control: bool = False) -> tuple:
    """Run one cell once: (result dict, Run)."""
    _listen_for_compiles()
    run = Run(cell, seed=seed, seconds=seconds, trace=trace,
              devices=devices, t0=t0, control=control)
    shutil.rmtree(os.path.join(run.root, run.out_dir), ignore_errors=True)
    os.makedirs(os.path.join(run.root, run.out_dir))
    run.phase("start")      # interpreter, JAX and the chip's runtime
    run.daemon = daemon.spawn(run.root, run.out_dir)
    proc, _, log = run.daemon
    try:
        cells.mode(run.traffic).run(run)
    finally:
        daemon.stop(proc, run.client)
        if run.client is not None:
            run.client.close()
        log.close()
    return result(run), run


def result(run: Run) -> dict:
    """The result line: metrics through their readers, the device, the
    breakdown of a traced run, and the numbers compared, last."""
    metrics = {}
    for m in run.cell["per_layer" if run.trace else "end_to_end"]:
        value = cells.metric_reader(m["name"]).read(run)
        if isinstance(value, tuple):
            value, note = value
            run.log(f"{m['name']}: {note}")
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = dict(device.describe(run.devices),
               memory_peak_bytes=run.record.get("memory_peak_bytes"))
    out = {"correct": run.correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": dev}
    traced = run.record["trace"]
    if run.trace and traced is not None:
        t, lo, hi = traced["trace"], traced["lo"], traced["hi"]
        dev["busy_s"] = tr.busy_ns(t, lo, hi) * 1e-9
        dev["window_s"] = (hi - lo) * 1e-9
        ops = sorted(tr.time_by_op(t, lo, hi).items(), key=lambda kv: -kv[1])
        out["breakdown"] = {
            "device_ops": [[k, v * 1e-9] for k, v in ops[:10]],
            "idle_gaps": [list(g) for g in tr.idle_gaps(t, lo, hi)]}
    # strict JSON has no infinity: a number that is not finite reads as
    # the largest double
    out["checks"] = {name: {"value": v if math.isfinite(v) else 1.7976931348623157e308,
                            "limit": lim}
                     for name, v, lim in run.checks}
    return out


def emit(out: dict, run: Run) -> None:
    for name, v, lim in run.checks:
        print(f"check {name} = {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


def elapsed(t0: float) -> float:
    return time.perf_counter() - t0
