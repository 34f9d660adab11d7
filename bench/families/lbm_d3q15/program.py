"""How the benchmark drives the paper's D3Q15 phase-field LBM (the
interface-tracking step of the conservative Allen-Cahn two-phase solver):
the generator's decision space, the inputs, and the public entry point."""
from __future__ import annotations

AXES = ("Z", "Y", "X")      # the sizes a cold-pricing mix may draw
# the grid a cold-pricing mix draws in the CPU tests
TINY_AXES = {"Z": {"values": [8, 16]}, "Y": {"values": [16, 32]},
             "X": {"values": [128, 256]}}


def tiny(config: dict) -> dict:
    """The configuration at a size the CPU tests run in interpret mode;
    Y = 32 keeps two ytile candidates (ty 8 and 16) beside replane."""
    return dict(config, domain=[8, 32, 128])


def shape(config: dict) -> dict:
    return {"domain": tuple(config["domain"]), "dtype": config["dtype"],
            "tau": config["tau"], "kappa": config["kappa"]}


def axes(shape: dict) -> dict:
    return dict(zip(AXES, shape["domain"]))


def with_axes(shape: dict, values: dict) -> dict:
    return dict(shape, domain=tuple(values[a] for a in AXES))


def label(shape: dict) -> str:
    z, y, x = shape["domain"]
    return f"lbm_d3q15_{z}x{y}x{x}"


def _elem_bytes(shape) -> int:
    import numpy as np

    return np.dtype(shape["dtype"]).itemsize


def candidates(shape: dict) -> tuple:
    """(config, spec) pairs, traced by the generator."""
    from repro.kernels.lbm_d3q15.generator import candidate_specs

    return tuple(candidate_specs(shape["domain"], _elem_bytes(shape)))


def slug(cfg: dict) -> str:
    return cfg["variant"] + (f"_ty{cfg['ty']}" if "ty" in cfg else "")


def inputs(shape: dict, key) -> tuple:
    """(pdf (15, Z, Y, X), phase (Z, Y, X)), made on the device in one
    jitted call.  The phase is uniform in [0, 1], so every cell is an
    interface cell and the sharpening term reaches every output; the PDFs
    are w_q * phase plus N(0, 1e-3) noise, off equilibrium, so a PDF pulled
    from the wrong cell shows."""
    import jax
    import jax.numpy as jnp

    from .ref import WEIGHTS

    dtype = shape["dtype"]

    def make(key):
        kp, kn = jax.random.split(key)
        w = jnp.asarray(WEIGHTS, dtype)[:, None, None, None]
        phase = jax.random.uniform(kp, shape["domain"], dtype)
        noise = 1e-3 * jax.random.normal(kn, (15, *shape["domain"]), dtype)
        return w * phase + noise, phase

    return jax.jit(make)(key)


def entry(shape: dict, cfg: dict):
    """The configuration ``cfg`` through the public ``lbm_step``, as a
    function named after it (the name its program carries in a trace).
    It returns the 15 new PDFs: the phase sum ``lbm_step`` returns beside
    them is not part of the kernel the paper prices, and is left out."""
    from repro.kernels.lbm_d3q15.ops import lbm_step

    tau, kappa = shape["tau"], shape["kappa"]

    def run(pdf, phase):
        return lbm_step(pdf, phase, tau=tau, kappa=kappa, config=cfg)[0]

    run.__name__ = run.__qualname__ = "bench_" + slug(cfg)
    return run
