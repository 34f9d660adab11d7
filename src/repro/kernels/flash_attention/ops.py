"""Jit'd public flash-attention API with estimator-selected blocks."""
from __future__ import annotations

from .generator import rank_configs
from .kernel import make_flash_attention, make_flash_decode

_CONFIG_CACHE: dict = {}


def flash_attention(q, k, v, causal: bool = True, config: dict | None = None):
    """q (B,Hq,Sq,D), k/v (B,Hkv,Skv,D); always runs the Pallas kernel.

    Sq and Skv must be multiples of 128 (Sq == 1 takes the decode kernel);
    other shapes raise, and callers that need them pick another path.
    """
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    if Skv % 128 or (Sq != 1 and Sq % 128):
        raise ValueError(
            f"flash_attention tiles Sq and Skv by 128; got Sq={Sq}, Skv={Skv}")
    if Sq == 1:
        bk = 512 if Skv % 512 == 0 else 128
        return make_flash_decode(B, Hq, Hkv, Skv, D, bk, q.dtype)(q, k, v)
    if config is None:
        key = (B, Hq, Hkv, Sq, Skv, D, causal, q.dtype.itemsize)
        config = _CONFIG_CACHE.get(key)
        if config is None:
            ranked = rank_configs(B, Hq, Hkv, Sq, Skv, D, causal,
                                  elem_bytes=q.dtype.itemsize)
            if not ranked:
                raise RuntimeError(
                    f"no feasible flash-attention configuration for "
                    f"{q.shape} x {k.shape}")
            config = ranked[0].config
            _CONFIG_CACHE[key] = config
    kern = make_flash_attention(
        B, Hq, Hkv, Sq, Skv, D, config["bq"], config["bk"], causal, q.dtype
    )
    return kern(q, k, v)
