"""Pallas TPU kernels for the weighted 2D 5-point Jacobi stencil.

dst[y, x] = wc * src[y, x] + wn * (src[y-1, x] + src[y+1, x]
                                   + src[y, x-1] + src[y, x+1])

on a halo-padded source.  Two variants whose configuration the estimator
selects analytically — and whose specs exist *only* through the tracing
frontend (DESIGN §9); nothing here is hand-lowered:

  * ``rowstream`` — grid over rows; three row refs (y, y+1, y+2 of the
    padded plane) supply the y-halo, x-halo via static slices.  Per-point
    affine accesses, so the frontend lowers it for the GPU backend.  Its
    one-row blocks do not tile on TPU, so the TPU space rejects it.
  * ``ytile``    — grid over y-tiles; two tile refs (j, j+1) supply the
    tile+halo rows via concatenation (the established tile+halo trick).
    Fewer grid steps, bigger blocks; y-halo rows are refetched.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pallas_call


def make_rowstream(domain: tuple, weights, dtype=jnp.float32):
    Y, X = domain
    Xp = X + 2
    wc, wn = (float(w) for w in weights)

    def kernel(r0, r1, r2, o_ref):
        def sl(row, x0):
            return row[0, x0:x0 + X]

        # access order mirrors the canonical 2d5pt spec: center, up, down,
        # left, right
        c = sl(r1, 1)
        u = sl(r0, 1)
        d = sl(r2, 1)
        le = sl(r1, 0)
        ri = sl(r1, 2)
        o_ref[0] = wc * c + wn * (u + d + le + ri)

    def call(src_padded):
        """src_padded: (Y + 2, X + 2)."""
        in_specs = [
            pl.BlockSpec((1, Xp), lambda y, k=k: (y + k, 0)) for k in range(3)
        ]
        return pallas_call(
            kernel,
            grid=(Y,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, X), lambda y: (y, 0)),
            out_shape=jax.ShapeDtypeStruct((Y, X), dtype),
        )(*([src_padded] * 3))

    return call


def make_ytile(domain: tuple, ty: int, weights, dtype=jnp.float32):
    Y, X = domain
    if Y % ty or ty < 2:
        raise ValueError("ty must divide Y and be >= 2")
    ny = Y // ty
    Xp = X + 2
    wc, wn = (float(w) for w in weights)

    def kernel(a_ref, b_ref, o_ref):
        rows = jnp.concatenate([a_ref[...], b_ref[...]], axis=0)

        def sl(y0, x0):
            return rows[y0:y0 + ty, x0:x0 + X]

        o_ref[...] = wc * sl(1, 1) + wn * (sl(0, 1) + sl(2, 1)
                                           + sl(1, 0) + sl(1, 2))

    def call(src_padded_y):
        """src_padded_y: ((ny + 1) * ty, X + 2) — 1 halo row at the top,
        padded to a whole extra tile at the bottom (ops.py prepares it)."""
        return pallas_call(
            kernel,
            grid=(ny,),
            in_specs=[
                pl.BlockSpec((ty, Xp), lambda j: (j, 0)),
                pl.BlockSpec((ty, Xp), lambda j: (j + 1, 0)),
            ],
            out_specs=pl.BlockSpec((ty, X), lambda j: (j, 0)),
            out_shape=jax.ShapeDtypeStruct((Y, X), dtype),
        )(src_padded_y, src_padded_y)

    return call


VARIANTS = ("rowstream", "ytile")


def make_kernel(variant: str, domain: tuple, weights=(0.5, 0.125),
                dtype=jnp.float32, ty=None):
    if variant == "rowstream":
        return make_rowstream(domain, weights, dtype)
    if variant == "ytile":
        return make_ytile(domain, ty or 8, weights, dtype)
    raise ValueError(f"unknown variant {variant}")
