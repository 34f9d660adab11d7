"""How the benchmark drives causal GQA flash attention (prefill): the
generator's (bq, bk) decision space, the inputs, and the public entry
point."""
from __future__ import annotations

AXES = ("B", "S")       # the sizes a cold-pricing mix may draw
# the grid a cold-pricing mix draws in the CPU tests
TINY_AXES = {"B": {"values": [1, 2]}, "S": {"values": [256, 384, 512]}}


def tiny(config: dict) -> dict:
    """The configuration at a size the CPU tests run in interpret mode."""
    return dict(config, run={"batch": 2, "seq": 256, "causal": True},
                num_attention_heads=4, num_key_value_heads=1)


def shape(config: dict) -> dict:
    run = config["run"]
    hq = config["num_attention_heads"]
    return {"batch": run["batch"], "q_heads": hq,
            "kv_heads": config["num_key_value_heads"],
            "seq": run["seq"], "head_dim": config["head_dim"],
            "causal": run["causal"], "dtype": config["torch_dtype"]}


def axes(shape: dict) -> dict:
    return {"B": shape["batch"], "S": shape["seq"]}


def with_axes(shape: dict, values: dict) -> dict:
    return dict(shape, batch=values["B"], seq=values["S"])


def label(shape: dict) -> str:
    return (f"gqa{shape['q_heads']}x{shape['kv_heads']}_b{shape['batch']}"
            f"_s{shape['seq']}_d{shape['head_dim']}")


def _elem_bytes(shape) -> int:
    import jax.numpy as jnp

    return jnp.dtype(shape["dtype"]).itemsize


def candidates(shape: dict) -> tuple:
    """(config, spec) pairs, traced by the generator."""
    from repro.kernels.flash_attention.generator import candidate_specs

    s = shape
    return tuple(candidate_specs(s["batch"], s["q_heads"], s["kv_heads"],
                                 s["seq"], s["seq"], s["head_dim"],
                                 s["causal"], _elem_bytes(s)))


def slug(cfg: dict) -> str:
    return f"bq{cfg['bq']}_bk{cfg['bk']}"


def inputs(shape: dict, key) -> tuple:
    """q, k, v, made on the device in one jitted call."""
    import jax

    s = shape
    b, n, d = s["batch"], s["seq"], s["head_dim"]

    def make(key):
        kq, kk, kv = jax.random.split(key, 3)
        return (jax.random.normal(kq, (b, s["q_heads"], n, d), s["dtype"]),
                jax.random.normal(kk, (b, s["kv_heads"], n, d), s["dtype"]),
                jax.random.normal(kv, (b, s["kv_heads"], n, d), s["dtype"]))

    return jax.jit(make)(key)


def entry(shape: dict, cfg: dict):
    """The configuration ``cfg`` through the public ``flash_attention``, as
    a function named after it (the name its program carries in a trace)."""
    from repro.kernels.flash_attention.ops import flash_attention

    causal = shape["causal"]

    def run(q, k, v):
        return flash_attention(q, k, v, causal, config=cfg)

    run.__name__ = run.__qualname__ = "bench_" + slug(cfg)
    return run
