"""Pallas TPU kernel for a padded, tiled 2D transpose.

out[n, m] = in[m, n] on tile-padded operands: the caller pads (M, N) up to
tile multiples (ops.py), the kernel moves (bm, bn) tiles through VMEM and
writes their transposes, and the caller crops.  Zero arithmetic — a pure
data-movement kernel whose estimator value is the HBM-traffic/grid-overhead
tradeoff across tile shapes.  Both the TPU spec and the GPU per-point
address expressions (the dim-permuted access ``in[p1, p0]``) exist only
through the tracing frontend (DESIGN §9).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pallas_call


def make_transpose(M: int, N: int, bm: int, bn: int, dtype=jnp.float32):
    """Transpose an (M, N) array (tile-divisible) into (N, M)."""
    if M % bm or N % bn:
        raise ValueError("tile sizes must divide the padded operand dims")

    def kernel(x_ref, o_ref):
        o_ref[...] = jnp.transpose(x_ref[...])

    def call(x):
        return pallas_call(
            kernel,
            grid=(M // bm, N // bn),
            in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
            out_specs=pl.BlockSpec((bn, bm), lambda i, j: (j, i)),
            out_shape=jax.ShapeDtypeStruct((N, M), dtype),
        )(x)

    return call
