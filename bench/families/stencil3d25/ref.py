"""Plain ``jax.numpy`` reference of the range-r 3D star stencil, and its
control in the next lower precision.  Imports nothing of the program.

dst[z, y, x] = w0 * src[z, y, x] + sum over the three axes and the offsets
o = 1..r of w * src[.. -o ..] + w * src[.. +o ..], with zero halo; weights
ordered [centre, (z,-1), (z,+1), ..., (z,-r), (z,+r), (y, ..), (x, ..)].
"""
from __future__ import annotations


def _stencil(src, weights, r: int, dtype):
    import jax.numpy as jnp

    Z, Y, X = src.shape
    p = jnp.pad(src.astype(dtype), r)
    w = [jnp.asarray(x, dtype) for x in weights]

    def sl(dz, dy, dx):
        return p[r + dz:r + dz + Z, r + dy:r + dy + Y, r + dx:r + dx + X]

    out = w[0] * sl(0, 0, 0)
    i = 1
    for axis in range(3):
        for o in range(1, r + 1):
            for s in (-o, o):
                d = [0, 0, 0]
                d[axis] = s
                out = out + w[i] * sl(*d)
                i += 1
    return out


def reference(shape: dict, inputs: tuple):
    """The stencil in float32, as the configuration states."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda s: _stencil(s, shape["weights"], shape["r"],
                                   jnp.float32))
    return f(*inputs)


def control(shape: dict, inputs: tuple):
    """The same stencil in bfloat16, the next precision below float32."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda s: _stencil(s, shape["weights"], shape["r"],
                                   jnp.bfloat16).astype(jnp.float32))
    return f(*inputs)
