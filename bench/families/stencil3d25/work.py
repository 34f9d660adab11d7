"""What the star stencil needs, whatever variant implements it: each
point's 6r+1 taps (a multiply and an add each), the zero-padded input read
once and the output written once."""


def work(shape: dict) -> dict:
    import numpy as np

    r = shape["r"]
    z, y, x = shape["domain"]
    eb = np.dtype(shape["dtype"]).itemsize
    return {"flops": 2 * (6 * r + 1) * z * y * x,
            "bytes": eb * ((z + 2 * r) * (y + 2 * r) * (x + 2 * r)
                           + z * y * x)}
