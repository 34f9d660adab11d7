"""Jit'd estimator-tuned matmul with shape-keyed config cache."""
from __future__ import annotations

from .generator import rank_configs
from .kernel import make_matmul

_CONFIG_CACHE: dict = {}


def tuned_matmul(a, b, config: dict | None = None):
    """``a @ b`` through the Pallas kernel, blocks picked by the estimator
    unless ``config`` pins them; raises when no blocking tiles the shape."""
    M, K = a.shape
    K2, N = b.shape
    if K != K2:
        raise ValueError(f"contraction mismatch: {a.shape} @ {b.shape}")
    if config is None:
        key = (M, K, N, a.dtype.itemsize)
        config = _CONFIG_CACHE.get(key)
        if config is None:
            ranked = rank_configs(M, K, N, elem_bytes=a.dtype.itemsize)
            if not ranked:
                raise RuntimeError(
                    f"no feasible matmul blocking for {a.shape} @ {b.shape} "
                    f"(blocks are 128-multiples that divide each dim)")
            config = ranked[0].config
            _CONFIG_CACHE[key] = config
    return make_matmul(M, K, N, config["bm"], config["bk"], config["bn"], a.dtype)(a, b)
