"""Estimator-tuned blocked Pallas matmul.

Grid (i, j, k) with k innermost; f32 accumulator scratch; A revisited per
(i, k), B per (j, k) — the revisit analysis prices exactly the classic
block-size tradeoff (bigger bm/bn -> fewer B/A refetches vs VMEM pressure),
replacing the usual matmul autotuner.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_call


def make_matmul(M, K, N, bm, bk, bn, dtype=jnp.float32, out_dtype=None):
    if M % bm or K % bk or N % bn:
        raise ValueError("block sizes must divide the operand dims")
    out_dtype = out_dtype or dtype
    nk = K // bk

    def kernel(a_ref, b_ref, o_ref, acc):
        k = pl.program_id(2)

        @pl.when(k == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        acc[...] += jnp.dot(
            a_ref[...], b_ref[...], preferred_element_type=jnp.float32
        )

        @pl.when(k == nk - 1)
        def _():
            o_ref[...] = acc[...].astype(o_ref.dtype)

    def call(a, b):
        return pallas_call(
            kernel,
            grid=(M // bm, N // bn, nk),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            dots=[(bm, bn)],
        )(a, b)

    return call
