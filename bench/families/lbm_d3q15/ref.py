"""Plain ``jax.numpy`` reference of one D3Q15 phase-field LBM step (the
interface tracking of the conservative Allen-Cahn two-phase solver), and
its control in the next lower precision.  Imports nothing of the program.

With velocities c_q and weights w_q below, halo 1 of zeros around the
domain, and h_q, phi the PDFs and phase field:

    grad phi  7-point central difference, 0.5 * (phi[+1] - phi[-1]) per axis
    n         = grad phi / sqrt(|grad phi|^2 + 1e-12)
    h_q       = h_q[x - c_q]                              (pull)
    h_q^eq    = w_q * phi + w_q * kappa * phi (1 - phi) * (c_q . n)
    h_q'      = h_q - (h_q - h_q^eq) / tau

returning the 15 new PDFs (15, Z, Y, X), q in the order of ``VELOCITIES``.
There are no matmuls, so no matmul precision setting applies: every
operation is elementwise in the stated dtype.
"""
from __future__ import annotations

# D3Q15: rest, 6 axis neighbours, 8 corners (c_x, c_y, c_z)
VELOCITIES = (
    (0, 0, 0),
    (1, 0, 0), (-1, 0, 0), (0, 1, 0), (0, -1, 0), (0, 0, 1), (0, 0, -1),
    (1, 1, 1), (-1, -1, -1), (1, 1, -1), (-1, -1, 1),
    (1, -1, 1), (-1, 1, -1), (-1, 1, 1), (1, -1, -1),
)
WEIGHTS = (2 / 9,) + (1 / 9,) * 6 + (1 / 72,) * 8


def _step(pdf, phase, tau: float, kappa: float, dtype):
    import jax.numpy as jnp

    _, Z, Y, X = pdf.shape
    h = jnp.pad(pdf.astype(dtype), ((0, 0), (1, 1), (1, 1), (1, 1)))
    p = jnp.pad(phase.astype(dtype), 1)

    def at(a, dz, dy, dx):
        return a[..., 1 + dz:1 + dz + Z, 1 + dy:1 + dy + Y,
                 1 + dx:1 + dx + X]

    phi = at(p, 0, 0, 0)
    gx = 0.5 * (at(p, 0, 0, 1) - at(p, 0, 0, -1))
    gy = 0.5 * (at(p, 0, 1, 0) - at(p, 0, -1, 0))
    gz = 0.5 * (at(p, 1, 0, 0) - at(p, -1, 0, 0))
    norm = jnp.sqrt(gx * gx + gy * gy + gz * gz + 1e-12)
    nx, ny, nz = gx / norm, gy / norm, gz / norm
    sharp = kappa * phi * (1.0 - phi)
    out = []
    for q, (cx, cy, cz) in enumerate(VELOCITIES):
        w = WEIGHTS[q]
        hq = at(h[q], -cz, -cy, -cx)
        heq = w * phi + w * sharp * (cx * nx + cy * ny + cz * nz)
        out.append(hq - (hq - heq) / tau)
    return jnp.stack(out)


def reference(shape: dict, inputs: tuple):
    """The step in float32, as the configuration states."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda pdf, phase: _step(pdf, phase, shape["tau"],
                                         shape["kappa"], jnp.float32))
    return f(*inputs)


def control(shape: dict, inputs: tuple):
    """The same step in bfloat16, the next precision below float32."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda pdf, phase: _step(pdf, phase, shape["tau"],
                                         shape["kappa"], jnp.bfloat16)
                .astype(jnp.float32))
    return f(*inputs)
