"""Paper fig. 1 workflow on TPU: analytic config selection for the Pallas
kernels (the autotuning replacement) through the exploration engine — one
Explorer (and one invariant cache) prices every generator's decision space —
plus a correctness spot-check of the selected kernel against the jnp oracle
(interpreted on the CPU, compiled by Mosaic on a TPU)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import Explorer
from repro.kernels.flash_attention.generator import candidate_specs as fa_cands
from repro.kernels.lbm_d3q15.generator import candidate_specs as lbm_cands
from repro.kernels.matmul.generator import candidate_specs as mm_cands
from repro.kernels.stencil3d25.generator import candidate_specs as st_cands

from .common import emit, timed


def main():
    explorer = Explorer()
    reports = []

    def rank(name, cands):
        report, us = timed(explorer.rank_pallas, list(cands), workload=name)
        assert report.entries, f"no feasible config for {name}"
        reports.append(report)
        return report, us

    # stencil: paper domain; selection must flip ring -> ytile as planes grow
    for dom in [(512, 512, 640), (256, 2048, 2048)]:
        report, us = rank("stencil", st_cands(4, dom, elem_bytes=8))
        best = report.entries[0]
        emit(
            f"kernel_select/stencil/{dom[0]}x{dom[1]}x{dom[2]}",
            us,
            f"best={best.config};B_per_pt={best.estimate.bytes_per_work:.1f};"
            f"lim={best.limiter};n_cands={len(report.entries)};"
            f"vmem_skipped={len(report.skipped)}",
        )
    report, us = rank("lbm", lbm_cands((256, 256, 256), elem_bytes=8))
    emit("kernel_select/lbm/256cube", us,
         f"best={report.entries[0].config};"
         f"B_per_lup={report.entries[0].estimate.bytes_per_work:.0f}")
    report, us = rank("matmul", mm_cands(8192, 8192, 8192, elem_bytes=2))
    emit("kernel_select/matmul/8k", us,
         f"best={report.entries[0].config};"
         f"t={report.entries[0].estimate.total_time*1e3:.2f}ms;"
         f"lim={report.entries[0].limiter}")
    report, us = rank("flash", fa_cands(8, 32, 8, 4096, 4096, 128))
    emit("kernel_select/flash/4k", us,
         f"best={report.entries[0].config};"
         f"t={report.entries[0].estimate.total_time*1e3:.2f}ms")
    # aggregate over all generator sweeps (cache stats are per-sweep deltas)
    emit(
        "kernel_select/engine", 0.0,
        f"{sum(len(r.entries) for r in reports)} configs priced across "
        f"{len(reports)} sweeps; {sum(len(r.skipped) for r in reports)} skipped; "
        f"invariant cache: {sum(r.cache_stats['hits'] for r in reports)} hits / "
        f"{sum(r.cache_stats['misses'] for r in reports)} misses",
    )

    # correctness of a selected stencil config (small domain)
    from repro.kernels.stencil3d25.ops import star_stencil
    from repro.kernels.stencil3d25.ref import pad_input, star_stencil_ref, star_weights

    src = jax.random.normal(jax.random.PRNGKey(0), (6, 16, 32))
    w = star_weights(2)
    out, us = timed(star_stencil, src, w, 2)
    ref = star_stencil_ref(pad_input(src, 2), w, 2)
    ok = bool(np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5))
    emit("kernel_select/stencil_selected_correct", us, f"allclose={ok}")
    assert ok


if __name__ == "__main__":
    main()
