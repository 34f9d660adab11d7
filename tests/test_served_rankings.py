"""The served rankings of the chip benchmark's three decision spaces, at
their benchmark sizes, pinned to the bit: config order, limiter, predicted
total time (``float.hex``) and skip reasons, priced on the v5e model
through ``repro.api.price`` (no kernel runs).  A change to the TPU model
or to the engine's Pallas path that moves any served number fails here."""
import pytest

from repro.api import pallas_request, price
from repro.core.machines import TPU_V5E


def _stencil():
    from repro.kernels.stencil3d25.generator import candidate_specs

    return candidate_specs(4, (512, 512, 640), 4)


def _attention():
    from repro.kernels.flash_attention.generator import candidate_specs

    return candidate_specs(8, 32, 8, 4096, 4096, 128, True, 2)


def _lbm():
    from repro.kernels.lbm_d3q15.generator import candidate_specs

    return candidate_specs((256, 256, 256), 4)


def _cfg(variant, ty=None):
    return {"variant": variant} if ty is None else {"variant": variant,
                                                    "ty": ty}


STENCIL = [
    (_cfg("ring"), "HBM", "0x1.c1f2148bc3845p-10"),
    (_cfg("ytile_ring", 256), "HBM", "0x1.579813fad3170p-9"),
    (_cfg("ytile_ring", 128), "HBM", "0x1.6539bd2d66e80p-9"),
    (_cfg("ytile_ring", 64), "HBM", "0x1.807d0f928e8a1p-9"),
    (_cfg("ytile_ring", 32), "HBM", "0x1.b703b45cddce1p-9"),
    (_cfg("ytile_ring", 16), "HBM", "0x1.12087ef8be2b1p-8"),
    (_cfg("ytile_ring", 8), "HBM", "0x1.7f15c88d5cb32p-8"),
    (_cfg("replane"), "HBM", "0x1.1505b94e9d8a9p-7"),
]

ATTENTION = [
    ({"bq": 1024, "bk": 2048}, "MXU", "0x1.96c6df3142aa1p-7"),
    ({"bq": 512, "bk": 2048}, "MXU", "0x1.9d7cdbd7f1d5ep-7"),
    ({"bq": 1024, "bk": 1024}, "MXU", "0x1.9d7cdbd7f1d5ep-7"),
    ({"bq": 512, "bk": 1024}, "MXU", "0x1.aae8d525502d8p-7"),
    ({"bq": 256, "bk": 2048}, "MXU", "0x1.aae8d525502d8p-7"),
    ({"bq": 1024, "bk": 512}, "MXU", "0x1.aae8d525502d8p-7"),
    ({"bq": 256, "bk": 1024}, "MXU", "0x1.c5c0c7c00cdcdp-7"),
    ({"bq": 512, "bk": 512}, "MXU", "0x1.c5c0c7c00cdcdp-7"),
    ({"bq": 1024, "bk": 256}, "MXU", "0x1.c5c0c7c00cdcdp-7"),
    ({"bq": 256, "bk": 512}, "MXU", "0x1.fb70acf5863b6p-7"),
    ({"bq": 512, "bk": 256}, "MXU", "0x1.fb70acf5863b6p-7"),
    ({"bq": 1024, "bk": 128}, "MXU", "0x1.fb70acf5863b6p-7"),
    ({"bq": 256, "bk": 256}, "MXU", "0x1.33683bb03c7c4p-6"),
    ({"bq": 512, "bk": 128}, "MXU", "0x1.33683bb03c7c4p-6"),
    ({"bq": 128, "bk": 2048}, "HBM", "0x1.7d43ceeca2d76p-6"),
    ({"bq": 128, "bk": 1024}, "HBM", "0x1.981bc1875f86bp-6"),
    ({"bq": 256, "bk": 128}, "MXU", "0x1.9ec8061b2f396p-6"),
    ({"bq": 128, "bk": 512}, "HBM", "0x1.cdcba6bcd8e54p-6"),
    ({"bq": 128, "bk": 256}, "HBM", "0x1.1c95b893e5d13p-5"),
    ({"bq": 128, "bk": 128}, "HBM", "0x1.87f582fed88e5p-5"),
]

LBM = [
    (_cfg("replane"), "HBM", "0x1.65c6daa53dbe1p-9"),
    (_cfg("ytile", 128), "HBM", "0x1.16bc9e3d2898cp-8"),
    (_cfg("ytile", 64), "HBM", "0x1.1a179c90802eap-8"),
    (_cfg("ytile", 32), "HBM", "0x1.20cd99372f5a7p-8"),
    (_cfg("ytile", 16), "HBM", "0x1.2e3992848db22p-8"),
    (_cfg("ytile", 8), "HBM", "0x1.4911851f4a616p-8"),
]


@pytest.mark.parametrize("space, expected, skipped", [
    (_stencil, STENCIL, []),
    (_attention, ATTENTION, []),
    (_lbm, LBM, []),
], ids=["stencil3d25_r4", "mixtral_attn_4k", "lbm_d3q15_256"])
def test_served_ranking_is_pinned_at_benchmark_size(space, expected,
                                                    skipped):
    report = price(pallas_request(list(space()), TPU_V5E,
                                  workload="w")).report
    assert [(e.config, e.limiter, e.estimate.total_time.hex())
            for e in report.entries] == expected
    assert [(s.config, s.reason) for s in report.skipped] == skipped
