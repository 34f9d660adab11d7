"""Price-then-run check of the estimator's main path on one TPU chip.

    python chip_smoke.py [--seed N]

Prices four decision spaces through the pricing daemon (``python -m
repro.serve``, started as a CPU-only child), then runs the estimator's top
three configurations of each at full size on the chip through the public
``ops.py`` entry points, and checks every output against its ``ref.py``
oracle on the chip:

  * stencil3d25, r=4, (512, 512, 640) f32
  * lbm_d3q15, (256, 256, 256) f32
  * matmul 8192^3 bf16
  * causal GQA flash attention, B=8 Hq=32 Hkv=8 S=4096 D=128 bf16

Per candidate it prints the configuration, the predicted time, the compile
seconds and the median wall time of a few calls after warm-up.  These are
bring-up readings, not benchmark metrics.  The last line of the output is
one JSON object naming the device.  Without a TPU it exits non-zero before
any work.  The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says,
else to ``<checkout>/.jax-cache``; the daemon's files go to a fresh
``chiprun_out/chip_smoke/``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join("chiprun_out", "chip_smoke")   # relative to ROOT
TOP = 3
TIMED_CALLS = 5
# full deployment shapes (benchmarks/bench_kernel_select.py, at f32 where
# the paper ran f64, which the chip's Pallas cannot)
SHAPES = {
    "stencil3d25": dict(r=4, domain=(512, 512, 640)),
    "lbm_d3q15": dict(domain=(256, 256, 256)),
    "matmul": dict(n=8192),
    "flash_attention": dict(B=8, Hq=32, Hkv=8, S=4096, D=128),
}


def _device():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX's default device is "
                 f"{dev.platform!r}")
    return dev, len(jax.devices())


# --------------------------------------------------------------------------
# the four spaces: candidates (traced here), inputs, entry point, oracle
# --------------------------------------------------------------------------
def _stencil(key):
    import jax
    import jax.numpy as jnp

    from repro.kernels.stencil3d25.generator import candidate_specs
    from repro.kernels.stencil3d25.ops import star_stencil
    from repro.kernels.stencil3d25.ref import (
        pad_input, star_stencil_ref, star_weights)

    r, dom = SHAPES["stencil3d25"]["r"], SHAPES["stencil3d25"]["domain"]
    w = tuple(float(x) for x in jax.device_get(star_weights(r)))
    src = jax.random.normal(key, dom, jnp.float32)
    return dict(
        candidates=candidate_specs(r, dom, 4), args=(src,),
        run=lambda cfg: lambda s: star_stencil(s, w, r=r, config=cfg),
        ref=lambda s: star_stencil_ref(pad_input(s, r), w, r),
        tol=1e-4)


def _lbm(key):
    import jax
    import jax.numpy as jnp

    from repro.kernels.lbm_d3q15.generator import candidate_specs
    from repro.kernels.lbm_d3q15.ops import lbm_step
    from repro.kernels.lbm_d3q15.ref import WEIGHTS, lbm_step_ref, pad_inputs

    dom = SHAPES["lbm_d3q15"]["domain"]
    phase = jax.nn.sigmoid(jax.random.normal(key, dom, jnp.float32))
    pdf = jnp.stack([w * phase for w in WEIGHTS])
    return dict(
        candidates=candidate_specs(dom, 4), args=(pdf, phase),
        run=lambda cfg: lambda p, f: lbm_step(p, f, config=cfg)[0],
        ref=lambda p, f: lbm_step_ref(*pad_inputs(p, f))[0],
        tol=1e-4)


def _matmul(key):
    import jax
    import jax.numpy as jnp

    from repro.kernels.matmul.generator import candidate_specs
    from repro.kernels.matmul.ops import tuned_matmul
    from repro.kernels.matmul.ref import matmul_ref

    n = SHAPES["matmul"]["n"]
    ka, kb = jax.random.split(key)
    a = jax.random.normal(ka, (n, n), jnp.bfloat16)
    b = jax.random.normal(kb, (n, n), jnp.bfloat16)
    return dict(
        candidates=candidate_specs(n, n, n, 2), args=(a, b),
        run=lambda cfg: lambda x, y: tuned_matmul(x, y, config=cfg),
        ref=matmul_ref, tol=1e-2, relative=True)


def _flash(key):
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention.generator import candidate_specs
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref

    B, Hq, Hkv, S, D = SHAPES["flash_attention"].values()
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, Hq, S, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, Hkv, S, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, Hkv, S, D), jnp.bfloat16)
    # the full (8, 32, 4096, 4096) score tensor would take 17 GB: the
    # kernel runs at full shape, the oracle checks batch element 0
    return dict(
        candidates=candidate_specs(B, Hq, Hkv, S, S, D, True, 2),
        args=(q, k, v),
        run=lambda cfg: lambda x, y, z: flash_attention(x, y, z, True,
                                                        config=cfg),
        ref=lambda x, y, z: attention_ref(x[:1], y[:1], z[:1], True),
        compare=lambda out: out[:1], tol=3e-2)


SPACES = {"stencil3d25": _stencil, "lbm_d3q15": _lbm, "matmul": _matmul,
          "flash_attention": _flash}


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------
def _start_daemon(sock: str, cache: str, log) -> subprocess.Popen:
    """The pricing daemon, pinned to the CPU: only this process holds the
    chip."""
    from repro.core.engine.pool import host_env

    env = host_env()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.serve", "--socket", sock,
         "--cache-path", cache],
        env=env, cwd=ROOT, stdout=log, stderr=subprocess.STDOUT)
    deadline = time.monotonic() + 120
    while not os.path.exists(sock):
        if proc.poll() is not None:
            raise RuntimeError(f"pricing daemon exited {proc.returncode}; "
                               f"see {log.name}")
        if time.monotonic() > deadline:
            proc.kill()
            raise RuntimeError("pricing daemon did not start in 120 s")
        time.sleep(0.05)
    return proc


def _stop_daemon(proc: subprocess.Popen, client) -> None:
    try:
        client.shutdown_server()
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


def _price(client, spaces: dict, machine: str) -> dict:
    """Top picks per space, priced by the daemon."""
    from repro.api import pallas_request

    picks = {}
    for name, space in spaces.items():
        t = time.perf_counter()
        result = client.price(pallas_request(
            tuple(space["candidates"]), machine, workload=name))
        ranked = result.ranking(name, machine)
        print(f"# priced {name}: {len(ranked)} feasible, "
              f"{len(result.skipped)} skipped, "
              f"{time.perf_counter() - t:.3f} s client-side", flush=True)
        if len(ranked) < TOP:
            raise RuntimeError(f"{name}: only {len(ranked)} feasible picks")
        picks[name] = ranked[:TOP]
    return picks


def _check_compiled(compiled, what: str) -> None:
    """The kernel ran through Mosaic as a TPU custom call, not interpreted."""
    from repro.kernels import interpret_mode

    if interpret_mode() or "tpu_custom_call" not in compiled.as_text():
        raise RuntimeError(f"{what}: not compiled as a TPU kernel")


def _run_pick(name, space, entry, reference, kind) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = entry.config
    fn = jax.jit(space["run"](cfg))
    t = time.perf_counter()
    compiled = fn.lower(*space["args"]).compile()
    compile_s = time.perf_counter() - t
    _check_compiled(compiled, f"{name} {cfg}")
    out = compiled(*space["args"]).block_until_ready()
    walls = []
    for _ in range(TIMED_CALLS):
        t = time.perf_counter()
        compiled(*space["args"]).block_until_ready()
        walls.append(time.perf_counter() - t)
    got = space.get("compare", lambda o: o)(out).astype(jnp.float32)
    want = reference.astype(jnp.float32)
    if got.shape != want.shape:
        raise RuntimeError(f"{name} {cfg}: shape {got.shape} != {want.shape}")
    err = float(jnp.max(jnp.abs(got - want)))
    if space.get("relative"):
        err /= float(jnp.max(jnp.abs(want)))
    finite = bool(np.isfinite(err))
    row = dict(space=name, config=cfg,
               predicted_s=entry.estimate.total_time,
               compile_s=compile_s, wall_median_s=statistics.median(walls),
               max_err=err, tol=space["tol"],
               relative_err=bool(space.get("relative")), device=kind)
    print(f"{name} config={json.dumps(cfg)} "
          f"predicted_s={row['predicted_s']!r} compile_s={compile_s!r} "
          f"wall_median_s={row['wall_median_s']!r} max_err={err!r}"
          f"{' (relative)' if row['relative_err'] else ''} device={kind}",
          flush=True)
    if not finite or err > space["tol"]:
        raise RuntimeError(f"{name} {cfg}: max error {err} > {space['tol']}")
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    os.chdir(ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.kernels import use_compile_cache

    cache_dir = use_compile_cache(ROOT)     # before JAX is imported
    dev, count = _device()
    import jax

    from repro.core.machines import machine_for_device
    from repro.serve import PriceClient

    machine = machine_for_device(dev.device_kind)
    print(f"# device {dev.platform} {dev.device_kind} x{count}, priced as "
          f"{machine.name}; compile cache {cache_dir}", flush=True)
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)

    keys = jax.random.split(jax.random.PRNGKey(args.seed), len(SPACES))
    spaces = {name: make(key)
              for (name, make), key in zip(SPACES.items(), keys)}
    sock = os.path.join(OUT, "serve.sock")
    with open(os.path.join(OUT, "serve.log"), "w") as log:
        daemon = _start_daemon(sock, os.path.join(OUT, "serve.invcache"), log)
        client = None
        try:
            client = PriceClient(sock)
            picks = _price(client, spaces, machine.name)
        finally:
            if client is None:
                daemon.kill()
                daemon.wait()
            else:
                _stop_daemon(daemon, client)
                client.close()

    rows = []
    for name, space in spaces.items():
        with jax.default_matmul_precision("highest"):
            reference = jax.jit(space["ref"])(*space["args"])
        for entry in picks[name]:
            rows.append(_run_pick(name, space, entry, reference,
                                  dev.device_kind))
        del reference
    with open(os.path.join(OUT, "results.json"), "w") as f:
        json.dump(rows, f, indent=1)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
