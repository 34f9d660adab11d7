"""Stencil code generator + estimator coupling (paper fig. 1, on TPU).

``candidate_specs`` enumerates the generator's decision space (variant x
tile size) and — via the spec-extraction frontend (DESIGN §9) — *traces*
each candidate's actual Pallas kernel into the address-expression artifact
the estimator prices, before any code runs.  The generator no longer
hand-writes a single ``OperandSpec``: grids, block shapes, grid
dependences, and VMEM scratch residency all come out of the kernel builder
itself, so the spec cannot drift from the code.  Only the flop model stays
hand-pinned physics.  ``generate`` then materializes the winning kernel.
"""
from __future__ import annotations

from functools import lru_cache

from repro.frontend.candidates import traced_candidates
from repro.kernels import dtype_for
from repro.core.machines import TPUMachine, TPU_V5E
from repro.core.tpu_adapt import RankedPallasConfig, select_pallas_config


def _flops_per_point(r: int) -> float:
    return float(6 * r + 1) * 2.0  # mul + add per tap


def _space(r: int, domain: tuple):
    _Z, Y, _X = domain
    yield {"variant": "replane"}
    yield {"variant": "ring"}
    ty = max(2 * r, 8)
    while ty <= Y // 2:
        if Y % ty == 0:
            yield {"variant": "ytile_ring", "ty": ty}
        ty *= 2


@lru_cache(maxsize=None)
def _candidates(r: int, domain: tuple, elem_bytes: int) -> tuple:
    import jax.numpy as jnp

    from repro.frontend import CostModel, KernelBuild, arg, candidates

    from .kernel import make_kernel

    Z, Y, X = domain
    Yp, Xp = Y + 2 * r, X + 2 * r
    Zp = Z + 2 * r
    dtype = dtype_for(elem_bytes)
    fl = _flops_per_point(r)
    weights = (1.0,) * (6 * r + 1)  # codegen constants; irrelevant to specs

    def build(cfg):
        variant, ty = cfg["variant"], cfg.get("ty")
        call = make_kernel(variant, r, domain, weights, dtype, ty)
        if variant == "replane":
            return KernelBuild(
                call, (arg("src", (Zp, Yp, Xp), dtype),),
                name=f"star{r}_replane",
                operand_names=[f"src_p{k}" for k in range(2 * r + 1)]
                + ["dst"],
                costs=CostModel(vpu_elems_per_step=fl * Y * X,
                                vpu_shape=(Y, X), work_per_step=float(Y * X),
                                elem_bytes=elem_bytes))
        if variant == "ring":
            return KernelBuild(
                call, (arg("src", domain, dtype),),
                name=f"star{r}_ring", operand_names=["src", "dst"],
                costs=CostModel(vpu_elems_per_step=fl * Y * X * Z / Zp,
                                vpu_shape=(Y, X),
                                work_per_step=float(Y * X) * Z / Zp,
                                elem_bytes=elem_bytes))
        y_alloc = (Y // ty + 1) * ty
        return KernelBuild(
            call, (arg("src", (Zp, y_alloc, Xp), dtype),),
            name=f"star{r}_ytile{ty}",
            operand_names=["src_a", "src_b", "dst"],
            costs=CostModel(vpu_elems_per_step=fl * ty * X * Z / Zp,
                            vpu_shape=(ty, X),
                            work_per_step=float(ty * X) * Z / Zp,
                            elem_bytes=elem_bytes))

    return tuple(candidates(build, _space(r, domain)))


def candidate_specs(r: int, domain: tuple, elem_bytes: int = 4):
    """Yield (config, PallasKernelSpec) for every generator decision."""
    yield from traced_candidates(_candidates, r, tuple(domain),
                                 elem_bytes)


def traced_gpu_spec(r: int, domain: tuple, elem_bytes: int = 8):
    """GPU address expressions traced from the replane kernel body: one
    per-point Access per stencil tap (structurally identical to
    ``core.specs.star_stencil_3d``)."""
    import jax.numpy as jnp

    from repro.frontend import CostModel, arg, lower_gpu, trace_kernel

    from .kernel import make_replane

    Z, Y, X = domain
    dtype = dtype_for(elem_bytes)
    traced = trace_kernel(
        make_replane(r, tuple(domain), (1.0,) * (6 * r + 1), dtype),
        (arg("src", (Z + 2 * r, Y + 2 * r, X + 2 * r), dtype),),
        name=f"star3d_r{r}", out_names=("dst",), trace_body=True)
    return lower_gpu(traced, CostModel(flops_per_point=float(6 * r + 1)),
                     name=f"star3d_r{r}")


def rank_configs(
    r: int, domain: tuple, machine: TPUMachine = TPU_V5E, elem_bytes: int = 4
) -> list[RankedPallasConfig]:
    return select_pallas_config(candidate_specs(r, domain, elem_bytes), machine)


def generate(
    r: int,
    domain: tuple,
    weights,
    machine: TPUMachine = TPU_V5E,
    dtype=None,
    elem_bytes: int = 4,
):
    """Pick the best configuration analytically and build that kernel."""
    import jax.numpy as jnp

    from .kernel import make_kernel

    ranked = rank_configs(r, domain, machine, elem_bytes)
    if not ranked:
        raise RuntimeError("no feasible stencil configuration for this domain")
    best = ranked[0]
    cfg = best.config
    kern = make_kernel(
        cfg["variant"], r, domain, weights, dtype or jnp.float32, cfg.get("ty")
    )
    return kern, best
