"""How the benchmark drives the paper's range-r 3D star stencil: the
generator's decision space, the inputs, and the public entry point."""
from __future__ import annotations

AXES = ("Z", "Y", "X")      # the sizes a cold-pricing mix may draw
# the grid a cold-pricing mix draws in the CPU tests
TINY_AXES = {"Z": {"values": [8, 16]}, "Y": {"values": [16, 32]},
             "X": {"values": [128, 256]}}


def tiny(config: dict) -> dict:
    """The configuration at a size the CPU tests run in interpret mode."""
    return dict(config, domain=[16, 16, 128])


def shape(config: dict) -> dict:
    return {"r": config["radius"], "domain": tuple(config["domain"]),
            "dtype": config["dtype"], "weights": tuple(config["weights"])}


def axes(shape: dict) -> dict:
    return dict(zip(AXES, shape["domain"]))


def with_axes(shape: dict, values: dict) -> dict:
    return dict(shape, domain=tuple(values[a] for a in AXES))


def label(shape: dict) -> str:
    z, y, x = shape["domain"]
    return f"star{shape['r']}_{z}x{y}x{x}"


def _elem_bytes(shape) -> int:
    import numpy as np

    return np.dtype(shape["dtype"]).itemsize


def candidates(shape: dict) -> tuple:
    """(config, spec) pairs, traced by the generator."""
    from repro.kernels.stencil3d25.generator import candidate_specs

    return tuple(candidate_specs(shape["r"], shape["domain"],
                                 _elem_bytes(shape)))


def slug(cfg: dict) -> str:
    return cfg["variant"] + (f"_ty{cfg['ty']}" if "ty" in cfg else "")


def inputs(shape: dict, key) -> tuple:
    """The source field, made on the device in one jitted call."""
    import jax

    make = jax.jit(lambda k: (jax.random.normal(k, shape["domain"],
                                                shape["dtype"]),))
    return make(key)


def entry(shape: dict, cfg: dict):
    """The configuration ``cfg`` through the public ``star_stencil``, as a
    function named after it (the name its program carries in a trace)."""
    from repro.kernels.stencil3d25.ops import star_stencil

    r, w = shape["r"], shape["weights"]

    def run(src):
        return star_stencil(src, w, r=r, config=cfg)

    run.__name__ = run.__qualname__ = "bench_" + slug(cfg)
    return run
