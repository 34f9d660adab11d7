"""Quickstart: the paper's workflow end to end, in 60 seconds on CPU.

1. Describe a kernel by its *address expressions* (what a code generator has
   before emitting code).
2. Ask the analytical estimator to price every launch configuration — no
   compilation, no benchmarking, no GPU.
3. Inspect the predicted volumes/limiters; cross-check one config against the
   exact LRU cache-simulator oracle.
4. Do the same on the TPU side: select a Pallas block configuration
   analytically and run the selected kernel (interpreted on the CPU,
   compiled by Mosaic on a TPU) against the jnp oracle.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import A100, LaunchConfig, estimate_gpu, rank_gpu_configs
from repro.core.cachesim import simulate_l2_waves
from repro.core.machines import GPUMachine
from repro.core.specs import star_stencil_3d

# ---------------------------------------------------------------- GPU side
spec = star_stencil_3d(r=4, domain=(192, 192, 256))
print(f"kernel: {spec.name}, domain {spec.domain}, "
      f"{len(spec.accesses)} address expressions")

ranked = rank_gpu_configs(spec, A100, total_threads=1024)
print("\ntop-5 predicted configurations (of "
      f"{len(ranked)} candidates, ~{0.2:.1f}s each to price):")
for rc in ranked[:5]:
    e = rc.estimate
    print(f"  block={rc.launch.block} fold={rc.launch.folding}: "
          f"{e.perf_lups/1e9:6.1f} GLup/s  DRAM={e.dram_load_per_lup:5.1f}B/LUP "
          f"limiter={e.limiter}")
worst = ranked[-1]
print(f"  ... worst: block={worst.launch.block} "
      f"{worst.estimate.perf_lups/1e9:6.1f} GLup/s")

# cross-check the best config against the exact cache simulator (scaled
# machine so it runs in seconds)
small = GPUMachine(name="A100/8", n_sms=13, clock_hz=1.41e9,
                   l1_bytes=192 * 1024, l2_bytes=20 * 1024 * 1024 // 8,
                   dram_bw=175e9, l2_bw=625e9, peak_flops_dp=1.2e12)
spec_s = star_stencil_3d(r=4, domain=(48, 96, 128))
best = rank_gpu_configs(spec_s, small)[0]
sim = simulate_l2_waves(spec_s, best.launch, small)
print(f"\nvalidation vs LRU simulator ({best.launch.block}): "
      f"predicted {best.estimate.dram_load_per_lup:.1f} B/LUP, "
      f"simulated {sim['dram_load_bytes_per_lup']:.1f} B/LUP")

# ---------------------------------------------------------------- TPU side
import os

from repro.kernels import use_compile_cache

use_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax  # after the compile-cache environment is set

from repro.kernels.stencil3d25.generator import rank_configs as tpu_rank
from repro.kernels.stencil3d25.ops import star_stencil
from repro.kernels.stencil3d25.ref import pad_input, star_stencil_ref, star_weights

print("\nTPU (Pallas) config selection for the same stencil:")
for cfg, est in [(rc.config, rc.estimate) for rc in tpu_rank(4, (512, 512, 640), elem_bytes=8)[:3]]:
    print(f"  {cfg}: {est.bytes_per_work:5.1f} B/pt, limiter={est.limiter}, "
          f"VMEM={est.vmem_alloc_bytes/2**20:.0f} MiB")

src = jax.random.normal(jax.random.PRNGKey(0), (8, 16, 32))
w = star_weights(2)
out = star_stencil(src, w, r=2)           # config picked analytically
ref = star_stencil_ref(pad_input(src, 2), w, 2)
print(f"\nselected Pallas kernel matches oracle: "
      f"{np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5)}")
