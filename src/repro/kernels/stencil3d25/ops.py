"""Jit'd public API for the generated star-stencil kernels."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .generator import generate, rank_configs
from .kernel import make_kernel
from .ref import pad_input, star_weights


@functools.partial(jax.jit, static_argnames=("r", "variant", "ty", "weights"))
def _apply(src, *, weights: tuple, r: int, variant: str, ty):
    """weights are codegen constants (baked into the kernel), hence static.

    ``ring`` takes the source as it is and keeps its zero halo in VMEM;
    ``replane`` and ``ytile_ring`` take it zero-padded by r on every side
    in HBM, ``ytile_ring`` further down in y to whole tiles plus one."""
    Z, Y, X = src.shape
    kern = make_kernel(variant, r, (Z, Y, X), weights, src.dtype, ty)
    if variant == "ring":
        return kern(src)
    padded = pad_input(src, r)
    if variant == "ytile_ring":
        t = ty or max(2 * r, 8)
        ny = Y // t
        extra = (ny + 1) * t - (Y + 2 * r)
        padded = jnp.pad(padded, ((0, 0), (0, extra), (0, 0)))
    return kern(padded)


def star_stencil(src, weights=None, r: int = 4, config: dict | None = None):
    """Apply the range-r star stencil; configuration chosen by the estimator
    unless ``config={'variant':..., 'ty':...}`` pins it."""
    if weights is None:
        weights = star_weights(r, src.dtype)
    w_static = tuple(float(w) for w in jax.device_get(weights))
    if config is None:
        ranked = rank_configs(r, src.shape, elem_bytes=src.dtype.itemsize)
        if not ranked:
            raise RuntimeError("no feasible config")
        config = ranked[0].config
    return _apply(
        src, weights=w_static, r=r, variant=config["variant"], ty=config.get("ty")
    )
