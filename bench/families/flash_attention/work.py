"""What causal GQA attention needs, whatever blocks implement it: two
matmuls (scores and values, 2*D operations per pair each) over the pairs
the mask keeps, q, k and v read once and o written once."""


def work(shape: dict) -> dict:
    import jax.numpy as jnp

    b, hq, hkv = shape["batch"], shape["q_heads"], shape["kv_heads"]
    s, d = shape["seq"], shape["head_dim"]
    eb = jnp.dtype(shape["dtype"]).itemsize
    pairs = s * (s + 1) // 2 if shape["causal"] else s * s
    return {"flops": 4 * b * hq * pairs * d,
            "bytes": eb * (2 * b * hq * s * d + 2 * b * hkv * s * d)}
