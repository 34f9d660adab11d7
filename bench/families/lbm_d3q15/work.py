"""What one D3Q15 phase-field LBM step needs, whatever variant implements
it: the halo-padded PDFs and phase field read once, the 15 new PDFs
written once, and the arithmetic of the update's equations (see ``ref``).

Flops per lattice update, a velocity component of 0 or +-1 counted as a
sign and not a multiply: the gradient 6 (a difference and a halving per
axis), the normal's 1 / sqrt(|grad|^2 + eps) 7 (3 squares, 3 adds, one
reciprocal square root), the sharpening kappa * phi * (1 - phi) 3; per
PDF the equilibrium w * phi + (w * sharp) * (c . n) 4 and the relaxation
h - (h - h_eq) / tau 3, with c . n taking no operation for the rest
velocity (whose equilibrium is w * phi alone: 1), one multiply by the
reciprocal norm for the 6 axis velocities and 2 adds and that multiply
for the 8 corners.  16 + (1 + 3) + 6 * (1 + 4 + 3) + 8 * (3 + 4 + 3)
= 148.
"""

FLOPS_PER_UPDATE = 16 + (1 + 3) + 6 * (1 + 4 + 3) + 8 * (3 + 4 + 3)


def work(shape: dict) -> dict:
    import numpy as np

    z, y, x = shape["domain"]
    eb = np.dtype(shape["dtype"]).itemsize
    padded = (z + 2) * (y + 2) * (x + 2)
    return {"flops": FLOPS_PER_UPDATE * z * y * x,
            "bytes": eb * (15 * padded + padded + 15 * z * y * x)}
