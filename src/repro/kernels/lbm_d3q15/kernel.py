"""Pallas TPU kernels for the D3Q15 Allen-Cahn interface-tracking LBM.

The z-streaming of the pull scheme is expressed *entirely in the BlockSpec
index maps*: PDF q's input ref maps grid step t to padded plane t+1-cz(q),
so every PDF plane is fetched exactly once (revisit analysis gives fetch
multiplicity 1 per plane) — the TPU equivalent of the GPU's streaming-store
friendliness the paper measures.  x/y shifts stay in-plane via static slices
of the halo-padded planes.

Variants:
  * ``replane`` — 15 PDF plane refs + 3 phase plane refs; no scratch.
  * ``ytile``   — all fields y-tiled (2 refs each for the tile+halo trick)
    for domains whose planes violate the VMEM capacity (layer) condition.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pallas_call

from .ref import VELOCITIES, WEIGHTS


def _compute(pdf_tap, phase_tap, o_ref, tau, kappa):
    """Shared collide+stream math, stored PDF by PDF into ``o_ref[q, 0]``.

    ``pdf_tap(q, dy, dx)``: the output-sized window of PDF q's padded
    plane (already at the right z, pull scheme) shifted by (dy, dx).
    ``phase_tap(k, dy, dx)``: the same window of the phase plane at z-1+k.
    """
    phi = phase_tap(1, 0, 0)
    gx = 0.5 * (phase_tap(1, 0, 1) - phase_tap(1, 0, -1))
    gy = 0.5 * (phase_tap(1, 1, 0) - phase_tap(1, -1, 0))
    gz = 0.5 * (phase_tap(2, 0, 0) - phase_tap(0, 0, 0))
    inv = jax.lax.rsqrt(gx * gx + gy * gy + gz * gz + 1e-12)
    sharp = kappa * phi * (1.0 - phi)
    for qi, (cx, cy, cz) in enumerate(VELOCITIES):
        w = WEIGHTS[qi]
        h = pdf_tap(qi, -cy, -cx)
        cdotn = (cx * gx + cy * gy + cz * gz) * inv
        heq = w * phi + w * sharp * cdotn
        o_ref[qi, 0] = h - (h - heq) / tau


def make_replane(domain: tuple, tau: float = 0.8, kappa: float = 0.15, dtype=jnp.float32):
    Z, Y, X = domain
    Yp, Xp = Y + 2, X + 2

    def kernel(*refs):
        pdf_refs = refs[:15]
        phases = refs[15:18]
        o_ref = refs[18]

        def pdf_tap(q, dy, dx):
            return pdf_refs[q][0, 0, 1 + dy:1 + dy + Y, 1 + dx:1 + dx + X]

        def phase_tap(k, dy, dx):
            return phases[k][0, 1 + dy:1 + dy + Y, 1 + dx:1 + dx + X]

        _compute(pdf_tap, phase_tap, o_ref, tau, kappa)

    def call(pdf_padded, phase_padded):
        """pdf_padded (15, Z+2, Yp, Xp), phase_padded (Z+2, Yp, Xp)."""
        in_specs = []
        for q, (cx, cy, cz) in enumerate(VELOCITIES):
            in_specs.append(
                pl.BlockSpec(
                    (1, 1, Yp, Xp),
                    functools.partial(lambda q, cz, t: (q, t + 1 - cz, 0, 0), q, cz),
                )
            )
        for k in range(3):
            in_specs.append(
                pl.BlockSpec((1, Yp, Xp), functools.partial(lambda k, t: (t + k, 0, 0), k))
            )
        return pallas_call(
            kernel,
            name="lbm_d3q15_replane",
            grid=(Z,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((15, 1, Y, X), lambda t: (0, t, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((15, Z, Y, X), dtype),
        )(*([pdf_padded] * 15 + [phase_padded] * 3))

    return call


def make_ytile(domain: tuple, ty: int, tau: float = 0.8, kappa: float = 0.15, dtype=jnp.float32):
    """y-tiled variant: per field two y-blocks (tile j and j+1) supply the
    tile+halo rows; requires ty >= 2 and ty | Y.  ops.py pads y to
    (ny+1)*ty rows so block j+1 stays in bounds."""
    Z, Y, X = domain
    if Y % ty or ty < 2:
        raise ValueError("ty must divide Y and be >= 2")
    ny = Y // ty
    Xp = X + 2

    def kernel(*refs):
        pdf_a = refs[:15]
        pdf_b = refs[15:30]
        ph = refs[30:36]  # (m_a, m_b, c_a, c_b, p_a, p_b)
        o_ref = refs[36]
        # tile j and tile j+1 stacked hold the tile plus its halo rows
        def window(a, b, dy, dx):
            rows = jnp.concatenate([a, b], axis=0)
            return rows[1 + dy:1 + dy + ty, 1 + dx:1 + dx + X]

        phases = [(ph[2 * k][0], ph[2 * k + 1][0]) for k in range(3)]
        _compute(
            lambda q, dy, dx: window(pdf_a[q][0, 0], pdf_b[q][0, 0], dy, dx),
            lambda k, dy, dx: window(*phases[k], dy, dx),
            o_ref, tau, kappa)

    def call(pdf_padded, phase_padded):
        """pdf_padded (15, Z+2, (ny+1)*ty, Xp), phase same y alloc."""
        in_specs = []
        for dj in (0, 1):
            for q, (cx, cy, cz) in enumerate(VELOCITIES):
                in_specs.append(
                    pl.BlockSpec(
                        (1, 1, ty, Xp),
                        functools.partial(
                            lambda q, cz, dj, j, t: (q, t + 1 - cz, j + dj, 0), q, cz, dj
                        ),
                    )
                )
        for k in range(3):
            for dj in (0, 1):
                in_specs.append(
                    pl.BlockSpec(
                        (1, ty, Xp),
                        functools.partial(lambda k, dj, j, t: (t + k, j + dj, 0), k, dj),
                    )
                )
        args = [pdf_padded] * 30 + [phase_padded] * 6
        return pallas_call(
            kernel,
            name="lbm_d3q15_ytile",
            grid=(ny, Z),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((15, 1, ty, X), lambda j, t: (0, t, j, 0)),
            out_shape=jax.ShapeDtypeStruct((15, Z, Y, X), dtype),
        )(*args)

    return call


def make_kernel(variant: str, domain: tuple, ty=None, tau=0.8, kappa=0.15, dtype=jnp.float32):
    if variant == "replane":
        return make_replane(domain, tau, kappa, dtype)
    if variant == "ytile":
        return make_ytile(domain, ty or 8, tau, kappa, dtype)
    raise ValueError(variant)
