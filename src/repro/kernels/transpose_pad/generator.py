"""Padded-transpose generator: all specs traced, none hand-written.

The decision space is the tile shape (bm, bn) on the tile-padded operand.
TPU specs derive entirely from the trace (zero arithmetic, work = moved
elements); the GPU lowering recovers the dim-permuted per-point access
``in[p1, p0]`` from the traced ``jnp.transpose`` store, exercising the
frontend's dimension-mapping inference.
"""
from __future__ import annotations

from functools import lru_cache

from repro.frontend.candidates import traced_candidates
from repro.kernels import dtype_for
from repro.core.machines import TPUMachine, TPU_V5E
from repro.core.tpu_adapt import pow2_tiles, select_pallas_config


def pad_to_tiles(n: int, tile: int) -> int:
    return -(-n // tile) * tile


def _space(Mp: int, Np: int):
    for bm in pow2_tiles(8, min(Mp, 512)):
        if Mp % bm:
            continue
        for bn in pow2_tiles(8, min(Np, 512)):
            if Np % bn:
                continue
            yield {"bm": bm, "bn": bn}


@lru_cache(maxsize=None)
def _candidates(Mp: int, Np: int, elem_bytes: int) -> tuple:
    import jax.numpy as jnp

    from repro.frontend import CostModel, KernelBuild, arg, candidates

    from .kernel import make_transpose

    dtype = dtype_for(elem_bytes)
    costs = CostModel(elem_bytes=elem_bytes, flops_per_point=0.0)

    def build(cfg):
        bm, bn = cfg["bm"], cfg["bn"]
        return KernelBuild(
            make_transpose(Mp, Np, bm, bn, dtype),
            (arg("x", (Mp, Np), dtype),),
            name=f"transpose_{bm}x{bn}", out_names=("xt",),
            costs=costs, trace_body=True)

    return tuple(candidates(build, _space(Mp, Np)))


def candidate_specs(shape: tuple, elem_bytes: int = 4, tile: int = 8):
    """(config, spec) pairs for transposing ``shape``, padded to ``tile``
    multiples (the kernel's operand is the padded array)."""
    M, N = shape
    yield from traced_candidates(_candidates, pad_to_tiles(M, tile),
                                 pad_to_tiles(N, tile), elem_bytes)


@lru_cache(maxsize=None)
def traced_gpu_spec(shape: tuple, elem_bytes: int = 4, bm: int = 32,
                    bn: int = 32, name: str = "transpose_pad"):
    """Dim-permuted per-point GPU address expressions from the trace."""
    import jax.numpy as jnp

    from repro.frontend import CostModel, arg, lower_gpu, trace_kernel

    from .kernel import make_transpose

    M, N = shape
    Mp, Np = pad_to_tiles(M, bm), pad_to_tiles(N, bn)
    dtype = dtype_for(elem_bytes)
    traced = trace_kernel(
        make_transpose(Mp, Np, bm, bn, dtype),
        (arg("x", (Mp, Np), dtype),),
        name=name, out_names=("xt",), trace_body=True)
    return lower_gpu(traced, CostModel(flops_per_point=0.0), name=name)


def rank_configs(shape: tuple, machine: TPUMachine = TPU_V5E,
                 elem_bytes: int = 4):
    return select_pallas_config(candidate_specs(shape, elem_bytes), machine)
