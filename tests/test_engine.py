"""Exploration-engine tests: cache equivalence (bitwise vs direct
``estimate_gpu``), ranking determinism under the parallel path, skipped-config
accounting with strict mode, the multi-machine sweep front-end, and the
vectorized L1 walks against the per-warp loop oracle."""
import dataclasses

import pytest

from repro.core.access import LaunchConfig
from repro.core.engine import Explorer, InvariantCache, SkippedConfig, Workload
from repro.core.gridwalk import (
    walk_block_l1,
    walk_block_l1_fast,
    warp_sector_requests,
    warp_sector_requests_fast,
)
from repro.core.machines import A100, TPU_V5E, V100, GPUMachine
from repro.core.perfmodel import estimate_gpu
from repro.core.selector import (
    enumerate_gpu_configs,
    paper_block_sizes,
    paper_foldings,
    rank_gpu_configs,
)
from repro.core.specs import lbm_d3q15, star_stencil_3d, stencil_2d5pt

# 1/8-scaled A100 keeps wave sets small so the full paper grid stays cheap;
# the estimator is machine-parametric, so equivalence here is equivalence.
SMALL = GPUMachine(
    name="A100/8",
    n_sms=13,
    clock_hz=1.41e9,
    l1_bytes=192 * 1024,
    l2_bytes=20 * 1024 * 1024 // 8,
    dram_bw=1400e9 / 8,
    l2_bw=5000e9 / 8,
    peak_flops_dp=9.7e12 / 8,
)

SPEC = star_stencil_3d(r=2, domain=(24, 32, 64))


def _estimate_key(est):
    """Every float the model emits, for bitwise comparison."""
    return (
        est.perf_lups, est.limiter, tuple(sorted(est.limiter_rates.items())),
        est.l1_cycles_per_lup, est.l2_l1_load_per_lup, est.l2_l1_store_per_lup,
        est.dram_load_per_lup, est.dram_store_per_lup,
        est.dram_breakdown.compulsory, est.dram_breakdown.capacity,
        est.dram_breakdown.saved_y, est.dram_breakdown.saved_z,
        est.l2_breakdown.total,
    )


def test_explorer_bitwise_identical_to_direct_estimates_full_paper_grid():
    """Engine results over the full paper grid (paper_block_sizes() x
    paper_foldings()) must be bitwise-identical to direct estimate_gpu."""
    configs = [
        LaunchConfig(block=b, folding=f)
        for b in paper_block_sizes()
        for f in paper_foldings()
    ]
    assert len(configs) == len(paper_block_sizes()) * 3

    direct = []
    for cfg in configs:
        try:
            direct.append((cfg, estimate_gpu(SPEC, cfg, SMALL)))
        except (ValueError, RuntimeError):
            continue
    direct.sort(key=lambda t: -t[1].perf_lups)  # stable, like the seed path

    report = Explorer().rank_gpu(SPEC, SMALL, configs)
    assert len(report.entries) + len(report.skipped) == len(configs)
    assert len(report.entries) == len(direct)
    for entry, (cfg, est) in zip(report.entries, direct):
        assert entry.config == cfg
        assert _estimate_key(entry.estimate) == _estimate_key(est)


def test_parallel_ranking_deterministic_and_equal_to_serial():
    configs = enumerate_gpu_configs(1024)[::7]
    serial = Explorer().rank_gpu(SPEC, SMALL, configs)
    par1 = Explorer(parallel=True, max_workers=2).rank_gpu(SPEC, SMALL, configs)
    par2 = Explorer(parallel=True, max_workers=2).rank_gpu(SPEC, SMALL, configs)
    key = lambda rep: [(e.config, _estimate_key(e.estimate)) for e in rep.entries]
    assert key(par1) == key(serial)
    assert key(par1) == key(par2)


def test_invariant_cache_shares_structure_across_machines():
    cache = InvariantCache()
    ex = Explorer(cache=cache)
    configs = enumerate_gpu_configs(1024)[:6]
    ex.rank_gpu(SPEC, SMALL, configs)
    first_misses = cache.misses
    # same geometry, double L2: walks, block footprints, and wave structure
    # are all shared — no new structural work at all
    big_l2 = dataclasses.replace(SMALL, name="A100/8-2xL2",
                                 l2_bytes=2 * SMALL.l2_bytes)
    ex.rank_gpu(SPEC, big_l2, configs)
    assert cache.misses == first_misses
    # and the big-L2 ranking still reflects the different capacity model
    assert len(ex.rank_gpu(SPEC, big_l2, configs).entries) == 6


def test_skipped_configs_recorded_with_reason_and_strict_raises():
    # a zero-extent domain produces an empty wave -> ValueError inside the
    # DRAM stage; the engine must record it, not swallow it
    empty = SPEC.scale_domain((0, 8, 8))
    cfg = LaunchConfig(block=(32, 4, 8))
    report = Explorer().rank_gpu(empty, SMALL, [cfg])
    assert not report.entries
    assert len(report.skipped) == 1
    assert report.skipped[0].config == cfg
    assert "empty wave" in report.skipped[0].reason

    with pytest.raises(ValueError, match="empty wave"):
        Explorer().rank_gpu(empty, SMALL, [cfg], strict=True)

    # the back-compat wrapper surfaces the same accounting
    ranked = rank_gpu_configs(empty, SMALL, [cfg])
    assert list(ranked) == []
    assert len(ranked.skipped) == 1
    with pytest.raises(ValueError):
        rank_gpu_configs(empty, SMALL, [cfg], strict=True)


def test_explore_sweeps_gpu_and_tpu_machines_in_one_call():
    from repro.kernels.stencil3d25.generator import candidate_specs

    configs = [
        LaunchConfig(block=(32, 4, 8)), LaunchConfig(block=(64, 4, 4)),
        LaunchConfig(block=(16, 8, 8), folding=(1, 1, 2)),
    ]
    wl = Workload(
        name="stencil",
        gpu_spec=SPEC,
        gpu_configs=configs,
        tpu_candidates=list(candidate_specs(2, (64, 128, 256), elem_bytes=4)),
    )
    report = Explorer().explore([wl], [SMALL, V100, TPU_V5E])
    cells = report.cells()
    assert ("stencil", SMALL.name) in cells
    assert ("stencil", V100.name) in cells
    assert ("stencil", TPU_V5E.name) in cells
    # limiter attribution populated for every cell
    attribution = report.limiter_attribution()
    assert set(attribution) == set(cells)
    assert all(sum(v.values()) > 0 for v in attribution.values())
    # cross-machine table mentions every machine
    table = report.comparison_table()
    for m in (SMALL.name, V100.name, TPU_V5E.name):
        assert m in table
    # best per cell agrees with the cell ranking
    best = report.best("stencil", V100.name)
    assert best is report.ranking("stencil", V100.name)[0]


def test_explore_records_undefined_backend_pairs():
    wl = Workload(name="gpu-only", gpu_spec=SPEC,
                  gpu_configs=[LaunchConfig(block=(32, 4, 8))])
    report = Explorer().explore([wl], [SMALL, TPU_V5E])
    reasons = [s.reason for s in report.skipped
               if s.machine == TPU_V5E.name]
    assert any("no Pallas candidates" in r for r in reasons)


def test_pallas_infeasible_candidates_skipped_with_reason():
    from repro.kernels.stencil3d25.generator import candidate_specs

    cands = list(candidate_specs(4, (512, 2048, 2048), elem_bytes=8))
    report = Explorer().rank_pallas(cands, TPU_V5E)
    assert len(report.entries) + len(report.skipped) == len(cands)
    assert report.skipped, "huge planes must violate the VMEM layer condition"
    assert all("VMEM" in s.reason for s in report.skipped)
    # feasible ones ranked by predicted time
    times = [e.estimate.total_time for e in report.entries]
    assert times == sorted(times)


def test_pallas_degraded_bounds_are_the_rate_stage_hbm_floor():
    """``bound_rank`` prices a Pallas cell by its rows' ``hbm_time +
    overhead``, a lower bound on each total, and skips what the exact
    ranking skips, with the same reasons."""
    from repro.kernels.stencil3d25.generator import candidate_specs

    cands = list(candidate_specs(4, (512, 2048, 2048), elem_bytes=8))
    exact = Explorer()._rank_pallas(cands, TPU_V5E, workload="st")
    degraded = Explorer().bound_rank(
        [Workload(name="st", tpu_candidates=cands)], [TPU_V5E])
    assert exact.skipped, "huge planes must violate the VMEM layer condition"
    assert [(s.config, s.reason) for s in degraded.skipped] == \
        [(s.config, s.reason) for s in exact.skipped]
    by_config = {repr(e.config): e.estimate for e in exact.entries}
    assert sorted(by_config) == sorted(repr(e.config)
                                       for e in degraded.entries)
    for e in degraded.entries:
        est = by_config[repr(e.config)]
        assert (e.limiter, e.estimate) == ("bound", None)
        floor = est.hbm_time + est.grid_overhead
        assert e.perf == 1.0 / floor and floor <= est.total_time


def test_vectorized_walks_match_loop_oracle():
    cases = [
        (star_stencil_3d(r=1, domain=(13, 17, 33)), (32, 4, 8), (1, 1, 1)),
        (star_stencil_3d(r=2, domain=(24, 32, 64)), (16, 8, 8), (1, 1, 2)),
        (star_stencil_3d(r=1, domain=(13, 17, 33)), (3, 5, 7), (1, 2, 1)),  # clipped, non-16-multiple
        (lbm_d3q15(domain=(12, 20, 28)), (64, 4, 4), (1, 2, 1)),
        (stencil_2d5pt(domain=(40, 72)), (2, 64, 2), (2, 2, 1)),
    ]
    for spec, block, fold in cases:
        lc = LaunchConfig(block=block, folding=fold)
        assert walk_block_l1_fast(spec, lc) == walk_block_l1(spec, lc)
        assert warp_sector_requests_fast(spec, lc, 32) == \
            warp_sector_requests(spec, lc, 32)


def test_rank_gpu_configs_wrapper_matches_engine_and_reports():
    configs = enumerate_gpu_configs(1024)[:9]
    ranked = rank_gpu_configs(SPEC, SMALL, configs)
    assert [r.launch for r in ranked] == [e.config for e in ranked.report.entries]
    perfs = [r.perf for r in ranked]
    assert perfs == sorted(perfs, reverse=True)
    assert ranked.report.cache_stats["misses"] > 0
