"""Kernel packages + the generator entry-point registry.

Every kernel package couples a code generator to the estimator through one
uniform entry point: ``<package>.generator.candidate_specs(...)`` yields
``(config_dict, PallasKernelSpec)`` pairs — the decision space priced before
any code exists (paper fig. 1).  ``get_generator`` resolves that entry point
lazily by name, so consumers (the workload suite, benchmarks) discover
generators without importing every kernel package (and its jax dependency)
up front.
"""
from __future__ import annotations

import importlib
from typing import Callable

# name -> module holding candidate_specs; extend when adding a kernel package
GENERATOR_MODULES = {
    "flash_attention": "repro.kernels.flash_attention.generator",
    "jacobi2d": "repro.kernels.jacobi2d.generator",
    "lbm_d3q15": "repro.kernels.lbm_d3q15.generator",
    "matmul": "repro.kernels.matmul.generator",
    "stencil3d25": "repro.kernels.stencil3d25.generator",
    "transpose_pad": "repro.kernels.transpose_pad.generator",
}


def available_generators() -> list[str]:
    return sorted(GENERATOR_MODULES)


def get_generator(name: str) -> Callable:
    """Resolve ``candidate_specs`` of the named kernel generator."""
    if name not in GENERATOR_MODULES:
        raise KeyError(
            f"unknown kernel generator {name!r}; "
            f"choose from {available_generators()}"
        )
    mod = importlib.import_module(GENERATOR_MODULES[name])
    return mod.candidate_specs


def lazy_submodules(pkg_name: str, submodules: tuple) -> tuple:
    """PEP-562 ``(__getattr__, __dir__)`` pair for a kernel package: the
    jax-backed submodules load on first attribute access only."""

    def __getattr__(name):
        if name in submodules:
            return importlib.import_module(f"{pkg_name}.{name}")
        raise AttributeError(
            f"module {pkg_name!r} has no attribute {name!r}")

    def __dir__():
        return sorted(submodules)

    return __getattr__, __dir__


def interpret_mode() -> bool:
    """Run Pallas kernels in interpret mode exactly when JAX's default
    backend is the CPU (tests, host-only runs); on a TPU every kernel
    compiles through Mosaic."""
    import jax

    return jax.default_backend() == "cpu"


def use_compile_cache(checkout: str) -> str:
    """Keep JAX's persistent compilation cache where the environment says
    (``JAX_COMPILATION_CACHE_DIR``), else at ``<checkout>/.jax-cache``.

    Called by entry points that run kernels, before they import JAX, never
    at library import: it only sets the environment JAX reads when it is
    imported, so the parent of a pool or a child process stays off JAX.
    The path is part of the cache key, so it is fixed, never temporary.
    Every compile is cached, however quick.  Child processes inherit both
    settings.  Returns the directory in use.
    """
    import os
    import sys

    if "jax" in sys.modules:
        raise RuntimeError("use_compile_cache() must run before jax is "
                           "imported: JAX reads the cache settings then")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    return os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(os.path.abspath(checkout), ".jax-cache"))


def pallas_call(kernel, *, grid, in_specs, out_specs, out_shape,
                scratch_shapes=(), dots=()):
    """``pl.pallas_call`` for this package's kernels.

    Interpret mode is read from the platform when the kernel is built, and
    Mosaic's VMEM limit is sized as the estimator's feasibility check
    counts it (``tpu_adapt.vmem_limit_bytes``): blocks, scratch, and the
    reserve for a copy of the input blocks or the f32 results of the
    body's dots, given as their ``(m, n)`` shapes in ``dots``.  So a
    candidate the estimator calls feasible gets the room it was priced
    with.
    """
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from repro.core.tpu_adapt import OperandSpec, vmem_limit_bytes

    outs = out_shape if isinstance(out_shape, (list, tuple)) else [out_shape]
    out_blocks = out_specs if isinstance(out_specs, (list, tuple)) \
        else [out_specs]

    def call(*args):
        blocks = [
            OperandSpec("", tuple(s.block_shape), np.dtype(x.dtype).itemsize,
                        is_output=i >= len(in_specs))
            for i, (s, x) in enumerate(zip(list(in_specs) + list(out_blocks),
                                           list(args) + list(outs)))
        ]
        scratch = sum(int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
                      for s in scratch_shapes)
        params = pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes(blocks, scratch, dots))
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch_shapes,
            interpret=interpret_mode(), compiler_params=params)(*args)

    return call


def dtype_for(elem_bytes: int):
    """The jnp dtype a generator's ``elem_bytes`` parameter denotes.

    One shared table for every kernel generator (they trace their builders
    with shape/dtype placeholders, so the byte size must round-trip through
    a real dtype).  Unsupported sizes get an actionable error instead of a
    KeyError from deep inside a cached candidate enumeration.
    """
    import jax.numpy as jnp

    table = {1: "int8", 2: "bfloat16", 4: "float32", 8: "float64"}
    if elem_bytes not in table:
        raise ValueError(
            f"unsupported elem_bytes {elem_bytes}; "
            f"choose from {sorted(table)}")
    return jnp.dtype(table[elem_bytes])
