"""Generated Pallas TPU kernels for the range-r 3D star stencil.

Three code-generation variants (DESIGN §3.1) whose configuration the
Warpspeed-TPU estimator selects analytically:

  * ``replane``    — naive plane streaming: 2r+1 full-plane input refs per
    step; no scratch.  The "bad but simple" configuration.
  * ``ring``       — single leading-plane ref + VMEM ring buffer of 2r+1
    planes; HBM volume is one load + one store per point (beats GPU caches —
    the software-managed layer condition).  Requires the full-plane working
    set to fit VMEM.
  * ``ytile_ring`` — ring variant with y-tiling for domains whose planes
    violate the VMEM layer condition; trades 2x halo refetch for residency.

All variants keep x/y halos in-plane via static slices of padded planes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_call


def _apply_star(plane, weights, r, Y, X, y0, x0):
    """Weighted star sum.  ``plane(dz) -> (ref, lead)`` names the ref and
    leading index holding the padded plane at z-offset dz; every tap is a
    static (Y, X) window sliced straight off that ref, its origin at
    (y0, x0) of the padded plane plus the tap's in-plane shift.
    """

    def tap(dz, dy, dx):
        ref, lead = plane(dz)
        return ref[lead, y0 + dy:y0 + dy + Y, x0 + dx:x0 + dx + X]

    out = weights[0] * tap(0, 0, 0)
    w = 1
    for axis in range(3):
        for o in range(1, r + 1):
            for s in (-o, o):
                shift = [0, 0, 0]
                shift[axis] = s
                out = out + weights[w] * tap(*shift)
                w += 1
    return out


def make_replane(r: int, domain: tuple, weights, dtype=jnp.float32):
    """Variant A: 2r+1 plane refs, no scratch."""
    Z, Y, X = domain
    Yp, Xp = Y + 2 * r, X + 2 * r
    weights = tuple(float(w) for w in weights)

    def kernel(*refs):
        planes = refs[: 2 * r + 1]
        o_ref = refs[2 * r + 1]

        o_ref[0] = _apply_star(lambda dz: (planes[dz + r], 0),
                               weights, r, Y, X, r, r)

    def call(src_padded):
        in_specs = [
            pl.BlockSpec((1, Yp, Xp), functools.partial(lambda k, t: (t + k, 0, 0), k))
            for k in range(2 * r + 1)
        ]
        return pallas_call(
            kernel,
            name="stencil3d25_replane",
            grid=(Z,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, Y, X), lambda t: (t, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((Z, Y, X), dtype),
        )(*([src_padded] * (2 * r + 1)))

    return call


def make_ring(r: int, domain: tuple, weights, dtype=jnp.float32):
    """Variant B: leading-plane ref + (2r+1)-plane VMEM ring buffer."""
    Z, Y, X = domain
    Yp, Xp = Y + 2 * r, X + 2 * r
    Zp = Z + 2 * r
    nring = 2 * r + 1
    weights = tuple(float(w) for w in weights)

    def kernel(s_ref, o_ref, ring):
        t = pl.program_id(0)
        ring[t % nring] = s_ref[0]

        @pl.when(t >= 2 * r)
        def _():
            # center plane is t - r (padded z coords); slot modulo ring
            o_ref[0] = _apply_star(lambda dz: (ring, (t - r + dz) % nring),
                                   weights, r, Y, X, r, r)

    def call(src_padded):
        return pallas_call(
            kernel,
            name="stencil3d25_ring",
            grid=(Zp,),
            in_specs=[pl.BlockSpec((1, Yp, Xp), lambda t: (t, 0, 0))],
            out_specs=pl.BlockSpec(
                (1, Y, X), lambda t: (jnp.maximum(t - 2 * r, 0), 0, 0)
            ),
            out_shape=jax.ShapeDtypeStruct((Z, Y, X), dtype),
            scratch_shapes=[pltpu.VMEM((nring, Yp, Xp), dtype)],
        )(src_padded)

    return call


def make_ytile_ring(r: int, domain: tuple, weights, ty: int, dtype=jnp.float32):
    """Variant C: ring buffer over y-tiles (fulfills the VMEM layer condition
    for large planes at the cost of 2x tile fetch)."""
    Z, Y, X = domain
    if Y % ty or ty < 2 * r:
        raise ValueError("ty must divide Y and be >= 2r")
    ny = Y // ty
    Xp = X + 2 * r
    Zp = Z + 2 * r
    nring = 2 * r + 1
    weights = tuple(float(w) for w in weights)
    # padded-y size must cover block j+1 (rows up to (ny+1)*ty)
    y_alloc = (ny + 1) * ty

    def kernel(a_ref, b_ref, o_ref, ring):
        t = pl.program_id(1)
        ring[t % nring] = jnp.concatenate([a_ref[0], b_ref[0]], axis=0)

        @pl.when(t >= 2 * r)
        def _():
            o_ref[0] = _apply_star(lambda dz: (ring, (t - r + dz) % nring),
                                   weights, r, ty, X, r, r)

    def call(src_padded_y):
        """src_padded_y: (Zp, y_alloc, Xp) — y padded by r at top and to
        y_alloc at the bottom (ops.py prepares this)."""
        return pallas_call(
            kernel,
            name="stencil3d25_ytile_ring",
            grid=(ny, Zp),
            in_specs=[
                pl.BlockSpec((1, ty, Xp), lambda j, t: (t, j, 0)),
                pl.BlockSpec((1, ty, Xp), lambda j, t: (t, j + 1, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, ty, X), lambda j, t: (jnp.maximum(t - 2 * r, 0), j, 0)
            ),
            out_shape=jax.ShapeDtypeStruct((Z, Y, X), dtype),
            scratch_shapes=[pltpu.VMEM((nring, 2 * ty, Xp), dtype)],
        )(src_padded_y, src_padded_y)

    return call


VARIANTS = ("replane", "ring", "ytile_ring")


def make_kernel(variant: str, r: int, domain: tuple, weights, dtype=jnp.float32, ty=None):
    if variant == "replane":
        return make_replane(r, domain, weights, dtype)
    if variant == "ring":
        return make_ring(r, domain, weights, dtype)
    if variant == "ytile_ring":
        return make_ytile_ring(r, domain, weights, ty or max(2 * r, 8), dtype)
    raise ValueError(f"unknown variant {variant}")
