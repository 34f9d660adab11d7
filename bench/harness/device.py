"""The chip: find it, name it, read its memory peak, and check that a
kernel compiled through Mosaic."""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(__file__), "peaks.json")


class NoAccelerator(SystemExit):
    """Raised (as a non-zero exit) when JAX finds no TPU or too few."""


def require_tpu(chips: int):
    """The devices of the cell.  Exits non-zero, before any work, where
    JAX's default backend is not a TPU or holds fewer than ``chips``."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(
            f"bench: needs a TPU; JAX's default device is "
            f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise NoAccelerator(
            f"bench: the cell asks for {chips} chips; JAX sees "
            f"{len(devices)}")
    return devices[:chips]


def describe(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest chip, where the backend says."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def peaks_for(kind: str) -> dict:
    """The published peaks of a ``device_kind``; unknown kinds are an
    error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(table)}")
    return table[kind]


def check_compiled(compiled, what: str) -> None:
    """The kernel ran through Mosaic as a TPU custom call, not
    interpreted."""
    if "tpu_custom_call" not in compiled.as_text():
        raise RuntimeError(f"{what}: not compiled as a TPU kernel")
