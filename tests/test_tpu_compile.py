"""The kernels the estimator picks compile for a TPU v5e at real sizes.

Nothing runs here: each test forces interpret mode off, lowers one kernel
through its public entry point with the configuration pinned, and compiles
it for one chip of a ``v5e:2x2`` topology that is described, not attached
(``repro.kernels.compile_probe``, whose CLI compiles every candidate).
Mosaic then refuses what the chip would refuse (unsupported primitives,
misaligned blocks, VMEM over the kernel's limit), at no chip time.

The topology is described inside a fixture only: loading the TPU compiler
at import would make the test workers collect different tests.  The tests
skip only where no TPU compiler is installed; any other failure to load it
fails them.
"""
import importlib.util

import jax
import pytest

from repro.kernels import compile_probe


@pytest.fixture(scope="module")
def one_chip():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler (libtpu) is installed")
    return compile_probe.one_chip()


@pytest.fixture
def mosaic(one_chip):
    """No persistent-cache writes this host cannot read back."""
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield one_chip
    jax.config.update("jax_enable_compilation_cache", prev)


def _top(name):
    from repro.core.tpu_adapt import select_pallas_config

    return select_pallas_config(
        compile_probe.SPACES[name].candidates())[0].config


@pytest.mark.parametrize("name,cfg", [
    pytest.param(name, None, id=f"{name}-top")
    for name in compile_probe.SPACES
] + [
    # 16 MiB of blocks and scratch: over Mosaic's default scoped VMEM limit
    # (16 MiB) with its internal scratch, so it compiles only with the
    # limit the kernel derives from its footprint
    pytest.param("matmul", {"bm": 1024, "bk": 1024, "bn": 1024},
                 id="matmul-1024-blocks"),
])
def test_compiles_for_v5e(mosaic, name, cfg):
    space = compile_probe.SPACES[name]
    compile_probe.compile_kernel(*space.build(cfg or _top(name), mosaic))


def test_flash_decode_compiles_for_v5e(mosaic):
    """Sq == 1 takes the decode kernel, with its one-row stat stores."""
    compile_probe.compile_kernel(*compile_probe.flash_decode(mosaic))
