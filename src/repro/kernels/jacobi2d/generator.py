"""Jacobi generator: the first kernel with NO hand-written specs at all.

Every candidate is traced from its Pallas builder; TPU operand specs, grid
dependences, *and* the cost model's VPU counts and work units are derived
from the traced body (DESIGN §9).  The GPU address expressions come from
the same trace — ``traced_gpu_spec`` lowers the rowstream body's five taps
into the classic per-point 5-point-stencil spec, so one traced kernel
prices on V100/A100/TPUv5e in a single ``Explorer.explore`` sweep.
"""
from __future__ import annotations

from functools import lru_cache

from repro.frontend.candidates import traced_candidates
from repro.kernels import dtype_for
from repro.core.machines import TPUMachine, TPU_V5E
from repro.core.tpu_adapt import select_pallas_config


FLOPS_PER_POINT = 5.0  # 4 adds + 1 mul equivalent (matches the paper's 2d5pt)


def _space(domain: tuple):
    Y, _X = domain
    yield {"variant": "rowstream"}
    ty = 8
    while ty <= Y // 2:
        if Y % ty == 0:
            yield {"variant": "ytile", "ty": ty}
        ty *= 2


@lru_cache(maxsize=None)
def _candidates(domain: tuple, elem_bytes: int) -> tuple:
    import jax.numpy as jnp

    from repro.frontend import CostModel, KernelBuild, arg, candidates

    from .kernel import make_kernel

    Y, X = domain
    dtype = dtype_for(elem_bytes)
    # vpu_elems / vpu_shape / work_per_step all derive from the traced body
    costs = CostModel(elem_bytes=elem_bytes, flops_per_point=FLOPS_PER_POINT)

    def build(cfg):
        variant, ty = cfg["variant"], cfg.get("ty")
        call = make_kernel(variant, domain, dtype=dtype, ty=ty)
        if variant == "rowstream":
            shape = (Y + 2, X + 2)
            name = "jacobi2d_rowstream"
        else:
            shape = ((Y // ty + 1) * ty, X + 2)
            name = f"jacobi2d_ytile{ty}"
        return KernelBuild(call, (arg("src", shape, dtype),), name=name,
                           out_names=("dst",), costs=costs, trace_body=True)

    return tuple(candidates(build, _space(domain)))


def candidate_specs(domain: tuple, elem_bytes: int = 4):
    yield from traced_candidates(_candidates, tuple(domain), elem_bytes)


@lru_cache(maxsize=None)
def traced_gpu_spec(domain: tuple, elem_bytes: int = 8,
                    name: str = "jacobi2d"):
    """Per-point GPU address expressions traced from the rowstream body."""
    import jax.numpy as jnp

    from repro.frontend import CostModel, arg, lower_gpu, trace_kernel

    from .kernel import make_rowstream

    Y, X = domain
    dtype = dtype_for(elem_bytes)
    traced = trace_kernel(
        make_rowstream(tuple(domain), (0.5, 0.125), dtype),
        (arg("src", (Y + 2, X + 2), dtype),),
        name=name, out_names=("dst",), trace_body=True)
    return lower_gpu(traced, CostModel(flops_per_point=FLOPS_PER_POINT),
                     name=name)


def rank_configs(domain: tuple, machine: TPUMachine = TPU_V5E,
                 elem_bytes: int = 4):
    return select_pallas_config(candidate_specs(domain, elem_bytes), machine)


def generate(domain: tuple, weights=(0.5, 0.125),
             machine: TPUMachine = TPU_V5E, elem_bytes: int = 4, dtype=None):
    import jax.numpy as jnp

    from .kernel import make_kernel

    ranked = rank_configs(domain, machine, elem_bytes)
    if not ranked:
        raise RuntimeError("no feasible jacobi2d configuration")
    best = ranked[0]
    kern = make_kernel(best.config["variant"], domain, weights,
                       dtype or jnp.float32, best.config.get("ty"))
    return kern, best
