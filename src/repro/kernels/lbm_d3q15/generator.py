"""LBM kernel generator + estimator coupling (paper §5.3 on TPU).

The D3Q15 replane candidate carries 19 operands and the y-tiled one 37 —
exactly the hand-maintained spec boilerplate the spec-extraction frontend
(DESIGN §9) exists to delete.  Every candidate is now traced from the
actual Pallas kernel: the z-streaming index maps (``t + 1 - cz`` per PDF),
the tile+halo double refs, and the output block all become address
expressions mechanically.  Only the collide+stream flop estimate remains a
hand-pinned physics constant.
"""
from __future__ import annotations

from functools import lru_cache

from repro.frontend.candidates import traced_candidates
from repro.kernels import dtype_for
from repro.core.machines import TPUMachine, TPU_V5E
from repro.core.tpu_adapt import select_pallas_config

FLOPS_PER_LUP = 15 * 8 + 25  # relax+equilibrium per PDF + gradient/normal math


def _space(domain: tuple):
    _Z, Y, _X = domain
    yield {"variant": "replane"}
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

    Z, Y, X = domain
    Yp, Xp = Y + 2, X + 2
    dtype = dtype_for(elem_bytes)

    def build(cfg):
        variant, ty = cfg["variant"], cfg.get("ty")
        call = make_kernel(variant, domain, ty, dtype=dtype)
        if variant == "replane":
            return KernelBuild(
                call,
                (arg("pdf", (15, Z + 2, Yp, Xp), dtype),
                 arg("phase", (Z + 2, Yp, Xp), dtype)),
                name="lbm_replane",
                operand_names=[f"pdf{q}" for q in range(15)]
                + [f"phase{k}" for k in range(3)] + ["dst"],
                costs=CostModel(
                    vpu_elems_per_step=float(FLOPS_PER_LUP * Y * X),
                    vpu_shape=(Y, X), work_per_step=float(Y * X),
                    elem_bytes=elem_bytes))
        y_alloc = (Y // ty + 1) * ty
        return KernelBuild(
            call,
            (arg("pdf", (15, Z + 2, y_alloc, Xp), dtype),
             arg("phase", (Z + 2, y_alloc, Xp), dtype)),
            name=f"lbm_ytile{ty}",
            operand_names=[f"pdf{q}_{dj}" for dj in (0, 1) for q in range(15)]
            + [f"phase{k}_{dj}" for k in range(3) for dj in (0, 1)] + ["dst"],
            costs=CostModel(
                vpu_elems_per_step=float(FLOPS_PER_LUP * ty * X),
                vpu_shape=(ty, X), work_per_step=float(ty * X),
                elem_bytes=elem_bytes))

    return tuple(candidates(build, _space(domain)))


def candidate_specs(domain: tuple, elem_bytes: int = 4):
    yield from traced_candidates(_candidates, tuple(domain), elem_bytes)


def rank_configs(domain: tuple, machine: TPUMachine = TPU_V5E, elem_bytes: int = 4):
    return select_pallas_config(candidate_specs(domain, elem_bytes), machine)


def generate(domain: tuple, machine: TPUMachine = TPU_V5E, elem_bytes: int = 4, **kw):
    from .kernel import make_kernel

    ranked = rank_configs(domain, machine, elem_bytes)
    if not ranked:
        raise RuntimeError("no feasible LBM configuration")
    best = ranked[0]
    kern = make_kernel(best.config["variant"], domain, best.config.get("ty"), **kw)
    return kern, best
