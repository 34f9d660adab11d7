"""Compile the generators' candidates for a TPU v5e that is described, not
attached.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.kernels.compile_probe [SPACE ...]

Nothing runs.  Each candidate of a space is built through its public
``ops.py`` entry point at the space's real size, with interpret mode off,
and compiled for one chip of a ``v5e:2x2`` topology, so Mosaic refuses what
the chip would refuse.  The probe holds the estimator's VMEM feasibility
to the compiler: every candidate it calls feasible compiles, and every one
it skips for VMEM does not.  It prints one line per candidate and exits 1
on any disagreement.

Only one process at a time may load the TPU compiler (it keeps a lock
until it exits): run no other probe, and not ``tests/test_tpu_compile.py``,
alongside.  The topology is described when ``one_chip`` is called, never
at import.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from dataclasses import dataclass
from typing import Callable

# the real sizes: the chip-smoke spaces (benchmarks/bench_kernel_select.py
# at f32 where the paper ran f64) plus the two traced-only generators
STENCIL = dict(r=4, domain=(512, 512, 640))
LBM = dict(domain=(256, 256, 256))
MATMUL = dict(n=8192)
FLASH = dict(B=8, Hq=32, Hkv=8, S=4096, D=128)
PLANE = (4096, 4096)


@dataclass(frozen=True)
class Space:
    """One generator's decision space at a real size."""

    candidates: Callable    # () -> [(config, PallasKernelSpec | RejectedSpec)]
    build: Callable         # (config, shape) -> (fn, [arg shapes])


def _stencil_candidates():
    from .stencil3d25.generator import candidate_specs

    return candidate_specs(STENCIL["r"], STENCIL["domain"], 4)


def _stencil(cfg, S):
    from .stencil3d25.ops import star_stencil

    r, dom = STENCIL["r"], STENCIL["domain"]
    w = (1.0 / (6 * r + 1),) * (6 * r + 1)
    return (lambda x: star_stencil(x, w, r=r, config=cfg),
            [S(dom, "float32")])


def _lbm_candidates():
    from .lbm_d3q15.generator import candidate_specs

    return candidate_specs(LBM["domain"], 4)


def _lbm(cfg, S):
    from .lbm_d3q15.ops import lbm_step

    dom = LBM["domain"]
    return (lambda pdf, phase: lbm_step(pdf, phase, config=cfg),
            [S((15, *dom), "float32"), S(dom, "float32")])


def _matmul_candidates():
    from .matmul.generator import candidate_specs

    n = MATMUL["n"]
    return candidate_specs(n, n, n, 2)


def _matmul(cfg, S):
    from .matmul.ops import tuned_matmul

    x = S((MATMUL["n"],) * 2, "bfloat16")
    return lambda a, b: tuned_matmul(a, b, config=cfg), [x, x]


def _flash_candidates():
    from .flash_attention.generator import candidate_specs

    B, Hq, Hkv, S, D = FLASH.values()
    return candidate_specs(B, Hq, Hkv, S, S, D, True, 2)


def _flash(cfg, S, Sq=None):
    from .flash_attention.ops import flash_attention

    B, Hq, Hkv, Skv, D = FLASH.values()
    kv = S((B, Hkv, Skv, D), "bfloat16")
    return (lambda q, k, v: flash_attention(q, k, v, True, config=cfg),
            [S((B, Hq, Sq or Skv, D), "bfloat16"), kv, kv])


def flash_decode(S):
    """One-token decode against the full KV cache: ``flash_attention``
    with Sq == 1 takes the decode kernel, which has no decision space."""
    return _flash(None, S, Sq=1)


def _jacobi_candidates():
    from .jacobi2d.generator import candidate_specs

    return candidate_specs(PLANE, 4)


def _jacobi(cfg, S):
    from .jacobi2d.ops import jacobi_step

    return lambda x: jacobi_step(x, config=cfg), [S(PLANE, "float32")]


def _transpose_candidates():
    from .transpose_pad.generator import candidate_specs

    return candidate_specs(PLANE, 4)


def _transpose(cfg, S):
    from .transpose_pad.ops import transpose

    return lambda x: transpose(x, config=cfg), [S(PLANE, "float32")]


SPACES = {
    "stencil3d25": Space(_stencil_candidates, _stencil),
    "lbm_d3q15": Space(_lbm_candidates, _lbm),
    "matmul": Space(_matmul_candidates, _matmul),
    "flash_attention": Space(_flash_candidates, _flash),
    "jacobi2d": Space(_jacobi_candidates, _jacobi),
    "transpose_pad": Space(_transpose_candidates, _transpose),
}


def one_chip():
    """Shape maker ``S(shape, dtype)`` placing arrays on one chip of a
    described ``v5e:2x2``.  Loads the TPU compiler, with its logs off."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=chip)


def compile_kernel(fn, args):
    """Compile ``fn`` for the described chip with interpret mode off, and
    check that the kernel became a TPU custom call."""
    import jax

    import repro.kernels

    interpret = repro.kernels.interpret_mode
    repro.kernels.interpret_mode = lambda: False
    try:
        compiled = jax.jit(fn).lower(*args).compile()
    finally:
        repro.kernels.interpret_mode = interpret
    if "tpu_custom_call" not in compiled.as_text():
        raise RuntimeError("no tpu_custom_call in the compiled program")
    return compiled


def probe(name: str, S) -> list:
    """Compile every candidate of one space.  Rows of ``(config, verdict,
    detail)``: verdict is ``ok`` when the compiler agrees with the
    estimator, ``MISMATCH`` when it does not, ``rejected`` for candidates
    the frontend rejected before pricing (never compiled)."""
    from repro.core.engine import RejectedSpec
    from repro.core.machines import TPU_V5E
    from repro.core.tpu_adapt import estimate_pallas

    space = SPACES[name]
    rows = []
    for config, spec in space.candidates():
        if isinstance(spec, RejectedSpec):
            rows.append((config, "rejected", spec.reason))
            continue
        feasible = estimate_pallas(spec, TPU_V5E).feasible
        t = time.perf_counter()
        try:
            compile_kernel(*space.build(config, S))
            agrees, why = feasible, "compiled"
        except Exception as e:  # noqa: BLE001 — the compiler's verdict
            # a skipped candidate must be refused for VMEM, not otherwise
            agrees = not feasible and "vmem" in str(e).lower()
            why = str(e).split(". ", 1)[0][:160]
        verdict = "ok" if agrees else "MISMATCH"
        rows.append((config, verdict,
                     f"feasible={feasible} {why} "
                     f"({time.perf_counter() - t:.1f} s)"))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("spaces", nargs="*", metavar="SPACE",
                    help=f"spaces to probe (default: all of {list(SPACES)})")
    args = ap.parse_args(argv)
    unknown = set(args.spaces) - set(SPACES)
    if unknown:
        ap.error(f"unknown spaces {sorted(unknown)}")
    S = one_chip()
    bad = 0
    for name in args.spaces or SPACES:
        for config, verdict, detail in probe(name, S):
            bad += verdict == "MISMATCH"
            print(f"{name} {config} {verdict}: {detail}", flush=True)
    print(f"# {bad} disagreement(s) between the estimator's VMEM check "
          f"and the v5e compiler")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
