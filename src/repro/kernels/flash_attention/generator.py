"""Flash-attention block-size selection via the analytical estimator.

Each (bq, bk) candidate traces the actual Pallas kernel (DESIGN §9): the
GQA head-packing index maps (``h // Hq``, ``(h % Hq) // group`` — quasi-
affine FloorDiv/Mod expressions), the K/V revisit structure, and the f32
running-stat scratch all come from the kernel builder.  The triangular
causal work factor stays a hand-pinned cost annotation: it is a property
of the masked *value space*, not of the address expressions.
"""
from __future__ import annotations

from functools import lru_cache

from repro.frontend.candidates import traced_candidates
from repro.kernels import dtype_for
from repro.core.machines import TPUMachine, TPU_V5E
from repro.core.tpu_adapt import MatmulShape, pow2_tiles, select_pallas_config


def _space(Sq, Skv):
    for bq in pow2_tiles(128, min(Sq, 1024)):
        if Sq % bq:
            continue
        for bk in pow2_tiles(128, min(Skv, 2048)):
            if Skv % bk:
                continue
            yield {"bq": bq, "bk": bk}


@lru_cache(maxsize=None)
def _candidates(B, Hq, Hkv, Sq, Skv, D, causal, elem_bytes) -> tuple:
    import jax.numpy as jnp

    from repro.frontend import CostModel, KernelBuild, arg, candidates

    from .kernel import make_flash_attention

    dtype = dtype_for(elem_bytes)
    tri = 0.5 if causal and Sq == Skv else 1.0  # triangular work/traffic

    def build(cfg):
        bq, bk = cfg["bq"], cfg["bk"]
        return KernelBuild(
            call=make_flash_attention(B, Hq, Hkv, Sq, Skv, D, bq, bk,
                                      causal, dtype),
            args=(arg("q", (B, Hq, Sq, D), dtype),
                  arg("k", (B, Hkv, Skv, D), dtype),
                  arg("v", (B, Hkv, Skv, D), dtype)),
            name=f"fa_{bq}x{bk}",
            out_names=("o",),
            costs=CostModel(
                matmuls_per_step=(MatmulShape(bq, D, bk),
                                  MatmulShape(bq, bk, D)),
                vpu_elems_per_step=6.0 * bq * bk * tri,  # exp, mask, rescale
                vpu_shape=(bq, bk),
                work_per_step=float(bq * bk) * tri,
                elem_bytes=elem_bytes))

    return tuple(candidates(build, _space(Sq, Skv)))


def candidate_specs(B, Hq, Hkv, Sq, Skv, D, causal=True, elem_bytes=2):
    yield from traced_candidates(_candidates, B, Hq, Hkv, Sq, Skv, D,
                                 bool(causal), elem_bytes)


def rank_configs(B, Hq, Hkv, Sq, Skv, D, causal=True, machine: TPUMachine = TPU_V5E,
                 elem_bytes=2):
    return select_pallas_config(
        candidate_specs(B, Hq, Hkv, Sq, Skv, D, causal, elem_bytes), machine
    )
