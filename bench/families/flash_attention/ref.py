"""Plain ``jax.numpy`` reference of causal GQA attention, and its control
in the next lower precision.  Imports nothing of the program.

o[b, h] = softmax(q[b, h] k[b, g]^T / sqrt(D), causal) v[b, g] with
g = h // (Hq / Hkv).  Computed in float32 at ``highest`` matmul precision,
one (batch, kv-head) block at a time, so the (S, S) scores of only one
group of heads are ever held.
"""
from __future__ import annotations


def _attention(q, k, v, causal: bool, cast):
    """q (B, Hq, S, D), k/v (B, Hkv, S, D) -> (B, Hq, S, D) float32.
    ``cast`` rounds the matmul operands (identity for the reference)."""
    import jax
    import jax.numpy as jnp

    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    g = Hq // Hkv
    qb = q.reshape(B * Hkv, g, S, D)
    kb = k.reshape(B * Hkv, S, D)
    vb = v.reshape(B * Hkv, S, D)
    mask = jnp.tril(jnp.ones((S, S), bool)) if causal else None

    def block(args):
        qi, ki, vi = args
        s = jnp.einsum("gqd,kd->gqk", cast(qi), cast(ki),
                       precision="highest",
                       preferred_element_type=jnp.float32) * (D ** -0.5)
        if causal:
            s = jnp.where(mask, s, -jnp.inf)
        p = jnp.exp(s - s.max(axis=-1, keepdims=True))
        o = jnp.einsum("gqk,kd->gqd", cast(p), cast(vi),
                       precision="highest",
                       preferred_element_type=jnp.float32)
        return o / p.sum(axis=-1, keepdims=True)

    out = jax.lax.map(block, (qb, kb, vb))
    return out.reshape(B, Hq, S, D)


def reference(shape: dict, inputs: tuple):
    """Attention in float32 from the bfloat16 inputs."""
    import jax
    import jax.numpy as jnp

    f32 = lambda x: x.astype(jnp.float32)
    f = jax.jit(lambda q, k, v: _attention(q, k, v, shape["causal"], f32))
    return f(*inputs)


def control(shape: dict, inputs: tuple):
    """The same attention with every matmul operand rounded to float8
    (e4m3), the next precision below bfloat16; output in bfloat16."""
    import jax
    import jax.numpy as jnp

    def fp8(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)

    f = jax.jit(lambda q, k, v: _attention(q, k, v, shape["causal"], fp8)
                .astype(jnp.bfloat16))
    return f(*inputs)
