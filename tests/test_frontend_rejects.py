"""Tracer rejection classes surface as actionable diagnostics.

One test per rejection class (non-affine index map, data-dependent grid,
data-dependent body addressing, scratch-staged GPU lowering): every class
must (a) raise/record a ``TraceError`` naming the offending access and (b)
flow through the exploration engine as a ``report.skipped`` reason rather
than an exception mid-sweep.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from repro.core.engine import Explorer, RejectedSpec, Workload
from repro.core.machines import TPU_V5E, V100
from repro.frontend import (
    KernelBuild,
    TraceError,
    arg,
    candidates,
    lower_gpu,
    price_kernel,
    trace_kernel,
)


def _copy_call(grid, in_spec, out_spec=None, shape=(32, 8)):
    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def call(x):
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[in_spec],
            out_specs=out_spec or pl.BlockSpec((8, 8), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
            interpret=True,
        )(x)

    return call


def _explore_skips(build):
    """Run one candidate through candidates() + Explorer; return skips."""
    pairs = list(candidates(lambda cfg: build, [{"case": 0}]))
    assert len(pairs) == 1
    assert isinstance(pairs[0][1], RejectedSpec)
    report = Explorer().explore(
        [Workload("rejected", tpu_candidates=pairs)], [TPU_V5E])
    assert not report.entries
    skips = report.skipped_for("rejected")
    assert len(skips) == 1
    return skips[0]


def test_reject_nonaffine_index_map():
    call = _copy_call((4,), pl.BlockSpec((8, 8), lambda i: (i * i, 0)))
    with pytest.raises(TraceError) as exc:
        trace_kernel(call, [arg("x", (128, 8))], name="quadratic")
    msg = str(exc.value)
    assert "operand 'x'" in msg and "non-affine" in msg
    skip = _explore_skips(KernelBuild(call, (arg("x", (128, 8)),),
                                      name="quadratic"))
    assert "non-affine" in skip.reason and "'x'" in skip.reason


def test_reject_data_dependent_grid():
    n = jnp.int32(4)  # a traced/array value, not a static Python int
    call = _copy_call((n,), pl.BlockSpec((8, 8), lambda i: (i, 0)))
    with pytest.raises(TraceError) as exc:
        trace_kernel(call, [arg("x", (32, 8))], name="dyngrid")
    assert "data-dependent grid" in str(exc.value)
    skip = _explore_skips(KernelBuild(call, (arg("x", (32, 8)),),
                                      name="dyngrid"))
    assert "data-dependent grid" in skip.reason


def test_reject_data_dependent_body_indexing():
    def kernel(x_ref, i_ref, o_ref):
        gather = x_ref[i_ref[0]]        # address depends on loaded data
        o_ref[...] = gather

    def call(x, idx):
        return pl.pallas_call(
            kernel,
            grid=(4,),
            in_specs=[pl.BlockSpec((8, 8), lambda i: (i, 0)),
                      pl.BlockSpec((1,), lambda i: (i,))],
            out_specs=pl.BlockSpec((8,), lambda i: (i,)),
            out_shape=jax.ShapeDtypeStruct((32,), jnp.float32),
            interpret=True,
        )(x, idx)

    with pytest.raises(TraceError) as exc:
        trace_kernel(call, [arg("x", (32, 8)), arg("idx", (4,), jnp.int32)],
                     name="gather", trace_body=True, require_body=True)
    msg = str(exc.value)
    assert "ref 'x'" in msg and "data-dependent" in msg
    # without require_body the diagnostic is recorded, not raised …
    traced = trace_kernel(
        call, [arg("x", (32, 8)), arg("idx", (4,), jnp.int32)],
        name="gather", trace_body=True)
    assert not traced.body.ok and "data-dependent" in traced.body.error
    # … and the GPU lowering turns it into a TraceError
    with pytest.raises(TraceError, match="data-dependent"):
        lower_gpu(traced)


def test_reject_scratch_staged_gpu_lowering():
    from repro.kernels.stencil3d25.kernel import make_ring

    traced = trace_kernel(
        make_ring(1, (8, 16, 32), (1.0,) * 7, jnp.float32),
        [arg("src", (8, 16, 32))], name="ring", trace_body=True)
    assert traced.body.ok
    with pytest.raises(TraceError, match="scratch"):
        lower_gpu(traced)


def test_untileable_blocks_leave_the_tpu_space():
    """One-row blocks of a 2D array do not tile on TPU: the jacobi
    rowstream candidate is rejected with the reason, y-tiles stay."""
    from repro.kernels.jacobi2d.generator import candidate_specs

    cands = dict((c["variant"] + str(c.get("ty", "")), s)
                 for c, s in candidate_specs((64, 256), 4))
    assert isinstance(cands["rowstream"], RejectedSpec)
    assert "does not tile on TPU" in cands["rowstream"].reason
    assert not isinstance(cands["ytile8"], RejectedSpec)


def test_price_kernel_reports_gpu_rejection():
    """A TPU-only-traceable kernel still prices on TPU; the GPU machines get
    the tracer's diagnostic as their skip reason."""
    from repro.kernels.stencil3d25.kernel import make_ring

    report = price_kernel(
        make_ring(1, (8, 16, 32), (1.0,) * 7, jnp.float32),
        [arg("src", (8, 16, 32))],
        machines=[V100, TPU_V5E], name="ring")
    assert report.best("ring", TPU_V5E.name) is not None
    skips = report.skipped_for("ring", V100.name)
    assert len(skips) == 1 and "scratch" in skips[0].reason


def test_reject_build_error_recorded():
    from repro.kernels.matmul.kernel import make_matmul

    def build(cfg):
        # 100 does not divide 128 -> builder raises ValueError
        return KernelBuild(make_matmul(128, 128, 128, 100, 128, 128),
                           (arg("a", (128, 128)), arg("b", (128, 128))),
                           name="bad")

    pairs = list(candidates(build, [{"bm": 100}]))
    assert isinstance(pairs[0][1], RejectedSpec)
    assert "build failed" in pairs[0][1].reason


def test_builder_postprocessing_gets_contract_diagnostic():
    """Cropping the pallas result inside the traced builder must produce the
    builder-contract diagnostic, not a bare TypeError from jax internals."""

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def call(x):
        out = pl.pallas_call(
            kernel,
            grid=(4,),
            in_specs=[pl.BlockSpec((8, 8), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 8), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((32, 8), jnp.float32),
            interpret=True,
        )(x)
        return out[:30, :]              # post-processing inside the builder

    with pytest.raises(TraceError, match="unmodified"):
        trace_kernel(call, [arg("x", (32, 8))], name="cropper")
