"""Jit'd public API for the LBM interface-tracking kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .generator import rank_configs
from .kernel import make_kernel


@functools.partial(jax.jit, static_argnames=("variant", "ty", "tau", "kappa"))
def _apply(pdf, phase, *, variant: str, ty, tau: float, kappa: float):
    """``replane`` takes the PDFs and phase as they are and makes its zero
    halo in VMEM; ``ytile`` takes them zero-padded by one on every side in
    HBM, and further down in y to whole tiles plus one."""
    q, Z, Y, X = pdf.shape
    if variant == "replane":
        return make_kernel(variant, (Z, Y, X), None, tau, kappa, pdf.dtype)(pdf, phase)
    pdf_p = jnp.pad(pdf, ((0, 0), (1, 1), (1, 1), (1, 1)))
    ph_p = jnp.pad(phase, ((1, 1), (1, 1), (1, 1)))
    ty_val = ty or 8
    ny = Y // ty_val
    extra = (ny + 1) * ty_val - (Y + 2)
    pdf_p = jnp.pad(pdf_p, ((0, 0), (0, 0), (0, extra), (0, 0)))
    ph_p = jnp.pad(ph_p, ((0, 0), (0, extra), (0, 0)))
    kern = make_kernel(variant, (Z, Y, X), ty_val, tau, kappa, pdf.dtype)
    return kern(pdf_p, ph_p)


def lbm_step(pdf, phase, tau: float = 0.8, kappa: float = 0.15, config: dict | None = None):
    """One pull-scheme interface-tracking step; config picked analytically."""
    if config is None:
        ranked = rank_configs(pdf.shape[1:], elem_bytes=pdf.dtype.itemsize)
        if not ranked:
            raise RuntimeError("no feasible config")
        config = ranked[0].config
    new_pdf = _apply(
        pdf, phase, variant=config["variant"], ty=config.get("ty"), tau=tau, kappa=kappa
    )
    return new_pdf, new_pdf.sum(axis=0)
