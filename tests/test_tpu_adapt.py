"""TPU estimator tests: revisit analysis, feasibility, config selection."""
import random

import pytest
from hypothesis_compat import given, settings, st  # skips property tests without hypothesis

from repro.core.machines import TPUMachine, TPU_V5E, machine_for_device
from repro.core.tpu_adapt import (
    VMEM_BASE_RESERVE_BYTES,
    MatmulShape,
    OperandSpec,
    PallasKernelSpec,
    estimate_pallas,
    fetch_count,
    fetch_count_oracle,
    linear_fetch_count,
    select_pallas_config,
    vmem_limit_bytes,
    vmem_reserve,
)


@given(
    st.lists(st.integers(1, 5), min_size=1, max_size=4),
    st.data(),
)
@settings(max_examples=120, deadline=None)
def test_fetch_count_matches_grid_walk(grid, data):
    grid = tuple(grid)
    nd = len(grid)
    deps = tuple(sorted(data.draw(st.sets(st.integers(0, nd - 1), max_size=nd))))
    fn = lambda *idx: tuple(idx[d] for d in deps)
    assert fetch_count(grid, deps) == fetch_count_oracle(grid, fn)


def test_vmem_padding_granularity():
    m = TPU_V5E
    op32 = OperandSpec("x", (1, 5, 100), elem_bytes=4)
    # pad 5 -> 8 sublanes, 100 -> 128 lanes
    assert op32.vmem_block_bytes(m) == 1 * 8 * 128 * 4
    op16 = OperandSpec("x", (1, 5, 100), elem_bytes=2)
    assert op16.vmem_block_bytes(m) == 1 * 16 * 128 * 2


def test_mxu_padding_penalty():
    m = TPU_V5E
    small = MatmulShape(8, 100, 100)
    assert small.padded_flops(m, elem_bytes=4) == 2 * 8 * 128 * 128
    assert small.padded_flops(m, elem_bytes=2) == 2 * 16 * 128 * 128


def test_layer_condition_feasibility():
    """Oversized working set -> infeasible (the VMEM layer condition)."""
    big = PallasKernelSpec(
        name="big", grid=(4,),
        operands=(OperandSpec("x", (1, 8192, 8192), 4, grid_deps=(0,)),),
    )
    assert not estimate_pallas(big).feasible
    small = PallasKernelSpec(
        name="small", grid=(4,),
        operands=(OperandSpec("x", (1, 128, 128), 4, grid_deps=(0,)),),
    )
    assert estimate_pallas(small).feasible
    # 100 MiB of double-buffered blocks fit 128 MiB, but not with the
    # compiler's reserve (a copy of the 50 MiB input block) beside them
    near = PallasKernelSpec(
        name="near", grid=(4,),
        operands=(OperandSpec("x", (1, 12800, 1024), 4, grid_deps=(0,)),),
    )
    assert not estimate_pallas(near).feasible
    # a feasible kernel's Mosaic limit grants the footprint plus the reserve
    est = estimate_pallas(small)
    assert vmem_limit_bytes(small.operands, 0) == \
        est.vmem_alloc_bytes + est.detail["vmem_reserve"]


def test_vmem_reserve_covers_inputs_or_dot_results():
    MiB = 1 << 20
    a = OperandSpec("a", (1024, 256), 2)
    b = OperandSpec("b", (256, 1024), 2)
    o = OperandSpec("o", (1024, 1024), 2, is_output=True)
    # 1 MiB of input blocks and 2 MiB of scratch, no dots: one copy of
    # what the body reads
    assert vmem_reserve((a, b, o), 2 * MiB, (), TPU_V5E) == \
        VMEM_BASE_RESERVE_BYTES + 3 * MiB
    # the (1024, 1024) f32 dot result (4 MiB) outweighs them
    assert vmem_reserve((a, b, o), 2 * MiB, [(1024, 1024)], TPU_V5E) == \
        VMEM_BASE_RESERVE_BYTES + 4 * MiB
    # the estimator and the kernel's limit count the same dots
    spec = PallasKernelSpec(
        name="mm", grid=(8, 8, 32), operands=(a, b, o),
        matmuls_per_step=(MatmulShape(1024, 256, 1024),),
        scratch_bytes=2 * MiB, elem_bytes=2)
    est = estimate_pallas(spec)
    assert est.detail["vmem_reserve"] == VMEM_BASE_RESERVE_BYTES + 4 * MiB
    assert vmem_limit_bytes(spec.operands, 2 * MiB, [(1024, 1024)]) == \
        est.vmem_alloc_bytes + est.detail["vmem_reserve"]


@pytest.mark.parametrize("grid,coeffs,walked", [
    ((2, 2), [[1, 1]], 3),              # (i + j,): (1, 0) repeats (0, 1)
    ((3, 4), [[4, 1]], 12),             # 4i + j: every step moves
    ((3, 4), [[1, 1], [0, 1]], 12),     # j alone tells the steps apart
    ((3, 2), [[1, 1]], 4),              # i + j on a longer grid
    ((2, 2, 2), [[1, 0, 1]], 7),        # i + k: (1, 0, 0) repeats (0, 1, 1)
    ((2, 5), [[0, 0]], 1),              # constant map
])
def test_linear_fetch_count_matches_walk(grid, coeffs, walked):
    def index_map(*g):
        return tuple(sum(c * x for c, x in zip(row, g)) for row in coeffs)

    assert fetch_count_oracle(grid, index_map) == walked
    assert linear_fetch_count(grid, coeffs) == walked


def test_device_kind_table():
    assert machine_for_device("TPU v5 lite") is TPU_V5E
    with pytest.raises(KeyError, match="no machine model"):
        machine_for_device("TPU v4")


def test_stencil_selector_prefers_ring_until_lc_breaks():
    from repro.kernels.stencil3d25.generator import rank_configs

    small = rank_configs(4, (128, 512, 512), elem_bytes=8)
    assert small[0].config["variant"] == "ring"
    big = rank_configs(4, (128, 4096, 4096), elem_bytes=8)
    assert big[0].config["variant"] == "ytile_ring"
    # ring must not even appear (infeasible)
    assert all(rc.config["variant"] != "ring" for rc in big)


def test_stencil_selector_keeps_ring_at_the_sweep_domain():
    """The benchmark's domain: ring, with its zero-bordered planes at the
    tile-aligned origin (8, 128), stays first and fits VMEM."""
    from repro.kernels.stencil3d25.generator import rank_configs

    best = rank_configs(4, (512, 512, 640), elem_bytes=4)[0]
    assert best.config == {"variant": "ring"}
    assert best.spec.scratch_bytes == 9 * (512 + 16) * (640 + 256) * 4
    est = best.estimate
    assert est.feasible
    assert est.vmem_alloc_bytes + est.detail["vmem_reserve"] \
        <= TPU_V5E.vmem_bytes


def test_matmul_selector_prefers_bigger_blocks():
    from repro.kernels.matmul.generator import rank_configs

    ranked = rank_configs(4096, 4096, 4096, elem_bytes=2)
    best, worst = ranked[0], ranked[-1]
    assert best.estimate.total_time < worst.estimate.total_time
    assert best.config["bm"] * best.config["bn"] > worst.config["bm"] * worst.config["bn"]


def test_estimate_hbm_volume_ring_vs_replane():
    from repro.kernels.stencil3d25.generator import candidate_specs

    specs = dict(
        (c["variant"], s) for c, s in candidate_specs(4, (64, 256, 256), 8)
        if c.get("ty") in (None, 16)
    )
    ring = estimate_pallas(specs["ring"])
    replane = estimate_pallas(specs["replane"])
    assert replane.hbm_bytes > 4 * ring.hbm_bytes
