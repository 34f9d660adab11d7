"""Code generation + analytical selection, the pystencils integration (§1.2).

Builds the paper's two applications — the range-4 3D25pt star stencil and the
D3Q15 Allen-Cahn LBM interface-tracking kernel — from their specs, prices the
generators' full decision space through the exploration engine in one
``repro.api.price()`` sweep, runs the selected kernels (interpreted on the
CPU, compiled by Mosaic on a TPU), and validates against the pure-jnp
oracles.

Run:  PYTHONPATH=src python examples/stencil_codegen.py
"""
import os

from repro.kernels import use_compile_cache

use_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# JAX reads the compile-cache environment when it is imported
import jax
import jax.numpy as jnp
import numpy as np

from repro.api import PriceRequest, price
from repro.core.engine import Workload
from repro.core.machines import TPU_V5E
from repro.kernels.lbm_d3q15.generator import candidate_specs as lbm_candidates
from repro.kernels.lbm_d3q15.ops import lbm_step
from repro.kernels.lbm_d3q15.ref import WEIGHTS, lbm_step_ref, pad_inputs
from repro.kernels.stencil3d25.generator import candidate_specs as st_candidates
from repro.kernels.stencil3d25.ops import star_stencil
from repro.kernels.stencil3d25.ref import pad_input, star_stencil_ref, star_weights

# ---- decision space for the paper's production domains -------------------
# one sweep prices both generators' candidate spaces; infeasible candidates
# (violated VMEM layer condition) land in report.skipped with their reason
report = price(PriceRequest(
    workloads=[
        Workload("stencil3d25",
                 tpu_candidates=list(st_candidates(4, (512, 512, 640),
                                                   elem_bytes=8))),
        Workload("lbm_d3q15",
                 tpu_candidates=list(lbm_candidates((256, 256, 256),
                                                    elem_bytes=8))[:5]),
    ],
    machines=[TPU_V5E],
)).report

print("stencil 3D25pt, domain (512, 512, 640), f64 — ranked candidates:")
for e in report.ranking("stencil3d25"):
    print(f"  {str(e.config):38s} {e.estimate.bytes_per_work:6.1f} B/pt  "
          f"t={e.estimate.total_time*1e3:7.2f} ms  {e.limiter}")
for s in report.skipped_for("stencil3d25"):
    print(f"  {str(s.config):38s} skipped: {s.reason}")

print("\nLBM D3Q15, domain (256, 256, 256), f64 — ranked candidates:")
for e in report.ranking("lbm_d3q15"):
    print(f"  {str(e.config):38s} {e.estimate.bytes_per_work:6.1f} B/LUP "
          f"t={e.estimate.total_time*1e3:7.2f} ms  {e.limiter}")

print(f"\nengine: {report.summary()}")

# ---- run the selected kernels on small domains and validate --------------
print(f"\nrunning selected kernels on {jax.default_backend()} vs oracles:")
src = jax.random.normal(jax.random.PRNGKey(0), (6, 16, 32))
w = star_weights(2)
out = star_stencil(src, w, r=2)
ref = star_stencil_ref(pad_input(src, 2), w, 2)
print(f"  stencil allclose: {np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5)}")

phase = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16)))
pdf = jnp.stack([wq * phase for wq in WEIGHTS])
new_pdf, new_phase = lbm_step(pdf, phase)
ref_pdf, ref_phase = lbm_step_ref(*pad_inputs(pdf, phase))
print(f"  lbm allclose:     {np.allclose(np.asarray(new_pdf), np.asarray(ref_pdf), atol=1e-5)}")
print(f"  phase conserved:  sum={float(new_phase.sum()):.4f} "
      f"(ref {float(ref_phase.sum()):.4f})")
