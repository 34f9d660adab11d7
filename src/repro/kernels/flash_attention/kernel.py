"""Estimator-tuned causal GQA flash attention (Pallas TPU).

Online-softmax streaming over KV blocks; f32 running stats in VMEM scratch.
Block sizes (bq, bk) are chosen by the analytical estimator: K/V refetch per
q-block vs VMEM residency — the same tradeoff the paper prices for thread
blocks.  Fully-masked causal KV blocks skip their compute via pl.when (the
estimator models the triangular work factor).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import pallas_call

NEG_INF = -1e30


def make_flash_attention(
    B, Hq, Hkv, Sq, Skv, D, bq, bk, causal=True, dtype=jnp.float32, scale=None
):
    if Sq % bq or Skv % bk:
        raise ValueError("bq | Sq and bk | Skv required")
    group = Hq // Hkv
    nk = Skv // bk
    scale = scale if scale is not None else D ** -0.5
    off = Skv - Sq  # causal diagonal offset (decode-style alignment)

    def kernel(q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s):
        qb = pl.program_id(1)
        kb = pl.program_id(2)

        @pl.when(kb == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)
            m_s[...] = jnp.full_like(m_s, NEG_INF)
            l_s[...] = jnp.zeros_like(l_s)

        def body():
            q = q_ref[0, 0].astype(jnp.float32)  # (bq, D)
            k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
            v = v_ref[0, 0].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale  # (bq, bk)
            if causal:
                rows = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + off
                cols = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
                s = jnp.where(cols <= rows, s, NEG_INF)
            m_prev = m_s[:, :1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_s[:, :1] = l_s[:, :1] * corr + p.sum(axis=-1, keepdims=True)
            acc[...] = acc[...] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            m_s[:, :1] = m_new

        if causal:
            # skip fully masked blocks (above the diagonal)
            pl.when(kb * bk <= qb * bq + bq - 1 + off)(body)
        else:
            body()

        @pl.when(kb == nk - 1)
        def _():
            denom = jnp.maximum(l_s[:, :1], 1e-30)
            o_ref[0, 0] = (acc[...] / denom).astype(o_ref.dtype)

    def call(q, k, v):
        """q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D)."""
        return pallas_call(
            kernel,
            grid=(B * Hq, Sq // bq, nk),
            in_specs=[
                pl.BlockSpec(
                    (1, 1, bq, D), lambda h, qb, kb: (h // Hq, h % Hq, qb, 0)
                ),
                pl.BlockSpec(
                    (1, 1, bk, D),
                    lambda h, qb, kb: (h // Hq, (h % Hq) // group, kb, 0),
                ),
                pl.BlockSpec(
                    (1, 1, bk, D),
                    lambda h, qb, kb: (h // Hq, (h % Hq) // group, kb, 0),
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, bq, D), lambda h, qb, kb: (h // Hq, h % Hq, qb, 0)
            ),
            out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), dtype),
            scratch_shapes=[
                pltpu.VMEM((bq, D), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
            ],
            dots=[(bq, bk), (bq, D)],
        )(q, k, v)

    return call


def make_flash_decode(B, Hq, Hkv, Skv, D, bk, dtype=jnp.float32, scale=None):
    """Single-token decode: q (B, Hq, 1, D) against a KV cache (B, Hkv, Skv, D)."""
    group = Hq // Hkv
    nk = Skv // bk
    scale = scale if scale is not None else D ** -0.5

    def kernel(q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s):
        kb = pl.program_id(1)

        @pl.when(kb == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)
            m_s[...] = jnp.full_like(m_s, NEG_INF)
            l_s[...] = jnp.zeros_like(l_s)

        q = q_ref[0, 0].astype(jnp.float32)      # (1, D)
        k = k_ref[0, 0].astype(jnp.float32)      # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                 # (1, bk)
        m_prev = m_s[:1, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_s[:1, :1] = l_s[:1, :1] * corr + p.sum(axis=-1, keepdims=True)
        acc[...] = acc[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_s[:1, :1] = m_new

        @pl.when(kb == nk - 1)
        def _():
            o_ref[0, 0] = (acc[...] / jnp.maximum(l_s[:1, :1], 1e-30)).astype(o_ref.dtype)

    def call(q, k, v):
        return pallas_call(
            kernel,
            grid=(B * Hq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, 1, D), lambda h, kb: (h // Hq, h % Hq, 0, 0)),
                pl.BlockSpec(
                    (1, 1, bk, D), lambda h, kb: (h // Hq, (h % Hq) // group, kb, 0)
                ),
                pl.BlockSpec(
                    (1, 1, bk, D), lambda h, kb: (h // Hq, (h % Hq) // group, kb, 0)
                ),
            ],
            out_specs=pl.BlockSpec((1, 1, 1, D), lambda h, kb: (h // Hq, h % Hq, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((B, Hq, 1, D), dtype),
            scratch_shapes=[
                pltpu.VMEM((1, D), jnp.float32),
                pltpu.VMEM((8, 128), jnp.float32),
                pltpu.VMEM((8, 128), jnp.float32),
            ],
            dots=[(1, bk), (1, D)],
        )(q, k, v)

    return call
