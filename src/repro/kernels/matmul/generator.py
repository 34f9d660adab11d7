"""Matmul block-size selection via the analytical estimator.

The decision space is traced, not hand-written (DESIGN §9): each (bm, bk,
bn) candidate builds the actual Pallas kernel, and the spec-extraction
frontend derives grid, operand address expressions, revisit structure,
scratch residency, *and the MXU matmul shape* (from the kernel body's
``jnp.dot``) mechanically.  Only the work-unit convention (1 MAC = 2 flops)
is pinned by hand — it is a modeling choice, not an address expression.
"""
from __future__ import annotations

from functools import lru_cache

from repro.frontend.candidates import traced_candidates
from repro.kernels import dtype_for
from repro.core.machines import TPUMachine, TPU_V5E
from repro.core.tpu_adapt import pow2_tiles, select_pallas_config


def _space(M, K, N):
    for bm in pow2_tiles(128, min(M, 1024)):
        if M % bm:
            continue
        for bn in pow2_tiles(128, min(N, 1024)):
            if N % bn:
                continue
            for bk in pow2_tiles(128, min(K, 2048)):
                if K % bk:
                    continue
                yield {"bm": bm, "bk": bk, "bn": bn}


@lru_cache(maxsize=None)
def _candidates(M, K, N, elem_bytes) -> tuple:
    import jax.numpy as jnp

    from repro.frontend import CostModel, KernelBuild, arg, candidates

    from .kernel import make_matmul

    dtype = dtype_for(elem_bytes)

    def build(cfg):
        bm, bk, bn = cfg["bm"], cfg["bk"], cfg["bn"]
        return KernelBuild(
            call=make_matmul(M, K, N, bm, bk, bn, dtype),
            args=(arg("a", (M, K), dtype), arg("b", (K, N), dtype)),
            name=f"mm_{bm}x{bk}x{bn}",
            out_names=("o",),
            # matmuls_per_step=None -> derived from the traced jnp.dot;
            # the accumulate runs on the MXU, so no VPU work is charged
            costs=CostModel(vpu_elems_per_step=0.0, vpu_shape=(),
                            work_per_step=2.0 * bm * bk * bn,
                            elem_bytes=elem_bytes),
            trace_body=True,
        )

    return tuple(candidates(build, _space(M, K, N)))


def candidate_specs(M, K, N, elem_bytes=2):
    yield from traced_candidates(_candidates, M, K, N, elem_bytes)


def traced_gpu_spec(M, K, N, elem_bytes=2):
    """GPU address-expression artifact traced from the Pallas kernel: the
    frontend's GEMM recognizer lowers it to the canonical MAC-domain spec
    (structurally identical to ``core.specs.matmul_naive``)."""
    import jax.numpy as jnp

    from repro.frontend import CostModel, arg, lower_gpu, trace_kernel

    from .kernel import make_matmul

    dtype = dtype_for(elem_bytes)
    bm = next(b for b in (128, 64, 32, M) if M % b == 0)
    bn = next(b for b in (128, 64, 32, N) if N % b == 0)
    bk = next(b for b in (128, 64, 32, K) if K % b == 0)
    traced = trace_kernel(
        make_matmul(M, K, N, bm, bk, bn, dtype),
        (arg("a", (M, K), dtype), arg("b", (K, N), dtype)),
        name=f"gemm_{M}x{K}x{N}", out_names=("o",), trace_body=True)
    return lower_gpu(traced, CostModel(flops_per_point=2.0, work_unit="MAC"),
                     name=f"gemm_{M}x{K}x{N}",
                     rename={"a": "A", "b": "B", "o": "C"})


def rank_configs(M, K, N, machine: TPUMachine = TPU_V5E, elem_bytes=2):
    return select_pallas_config(candidate_specs(M, K, N, elem_bytes), machine)
