"""Spec-extraction frontend: affine IR, tracing parity, lowering, new
traced-only kernels (DESIGN §9).

The parity tests freeze the pre-frontend hand-written specs inline and
assert the traced generators reproduce them *bitwise* — spec equality and
estimate-field equality — which is the acceptance contract for routing the
kernel generators through the tracer.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import specs
from repro.core.engine import Explorer, Workload
from repro.core.machines import A100, TPU_V5E, V100
from repro.core.tpu_adapt import (
    MatmulShape,
    OperandSpec,
    PallasKernelSpec,
    estimate_pallas,
    fetch_count,
    fetch_count_oracle,
    hbm_traffic,
)
from repro.frontend import (
    AffineExpr,
    CostModel,
    NonAffineError,
    Sym,
    affine,
    arg,
    grid_space,
    lower_gpu,
    lower_tpu,
    price_kernel,
    trace_kernel,
)

_EST_FIELDS = ("hbm_bytes", "hbm_time", "mxu_time", "vpu_time", "vmem_time",
               "vmem_alloc_bytes", "grid_overhead", "total_time", "limiter",
               "feasible", "work")


def assert_bitwise(traced_spec, hand_spec):
    assert traced_spec == hand_spec
    et, eh = estimate_pallas(traced_spec), estimate_pallas(hand_spec)
    for f in _EST_FIELDS:
        assert getattr(et, f) == getattr(eh, f), f


# --------------------------------------------------------------------------
# affine IR
# --------------------------------------------------------------------------
def test_affine_arithmetic():
    t = affine(Sym("g0"))
    e = 3 * t + 5 - 1
    assert e.eval({Sym("g0"): 4}) == 16
    assert e.free_syms() == frozenset({Sym("g0")})
    assert (e - e).is_const and (e - e).const == 0
    assert ((4 * t) // 4) == t
    assert ((4 * t + 2) % 2).is_const
    q = (t + 7) // 3
    assert q.eval({Sym("g0"): 2}) == 3
    m = (t + 7) % 3
    assert m.eval({Sym("g0"): 2}) == 0
    c = affine(10).clamp_lo(12)
    assert c.const == 12
    lo = (t - 4).clamp_lo(0)
    assert lo.eval({Sym("g0"): 1}) == 0 and lo.eval({Sym("g0"): 9}) == 5


def test_affine_rejections():
    t, u = affine(Sym("g0")), affine(Sym("g1"))
    with pytest.raises(NonAffineError):
        _ = t * u
    with pytest.raises(NonAffineError):
        _ = t // u
    with pytest.raises(NonAffineError):
        _ = 1 // t
    with pytest.raises(NonAffineError):
        _ = t / 2
    with pytest.raises(NonAffineError):
        int(t)
    with pytest.raises(NonAffineError):
        bool(t < u)
    with pytest.raises(NonAffineError):
        _ = t * (1 << 62) * 4  # overflow past the 64-bit address range


# --------------------------------------------------------------------------
# traced vs hand-written TPU specs (frozen from the pre-frontend generators)
# --------------------------------------------------------------------------
def _hand_stencil_replane(r, domain, elem_bytes):
    Z, Y, X = domain
    Yp, Xp = Y + 2 * r, X + 2 * r
    fl = float(6 * r + 1) * 2.0
    ops = tuple(
        OperandSpec(f"src_p{k}", (1, Yp, Xp), elem_bytes, grid_deps=(0,))
        for k in range(2 * r + 1)
    ) + (OperandSpec("dst", (1, Y, X), elem_bytes, grid_deps=(0,),
                     is_output=True),)
    return PallasKernelSpec(
        name=f"star{r}_replane", grid=(Z,), operands=ops,
        vpu_elems_per_step=fl * Y * X, vpu_shape=(Y, X),
        work_per_step=float(Y * X), elem_bytes=elem_bytes)


def _hand_stencil_ring(r, domain, elem_bytes):
    """The unpadded source plane by plane; the ring's planes carry their
    zero border, the interior at r rounded up to the (8, 128) tile."""
    Z, Y, X = domain
    oy, ox = -(-r // 8) * 8, -(-r // 128) * 128
    Zp = Z + 2 * r
    fl = float(6 * r + 1) * 2.0
    return PallasKernelSpec(
        name=f"star{r}_ring", grid=(Zp,),
        operands=(
            OperandSpec("src", (1, Y, X), elem_bytes, grid_deps=(0,)),
            OperandSpec("dst", (1, Y, X), elem_bytes, grid_deps=(0,),
                        is_output=True),
        ),
        vpu_elems_per_step=fl * Y * X * Z / Zp, vpu_shape=(Y, X),
        scratch_bytes=(2 * r + 1) * (Y + 2 * oy) * (X + 2 * ox) * elem_bytes,
        work_per_step=float(Y * X) * Z / Zp, elem_bytes=elem_bytes)


def test_stencil_traced_matches_handwritten():
    from repro.kernels.stencil3d25.generator import candidate_specs

    r, domain, eb = 2, (16, 64, 128), 4
    traced = {tuple(sorted(c.items())): s
              for c, s in candidate_specs(r, domain, eb)}
    assert_bitwise(traced[(("variant", "replane"),)],
                   _hand_stencil_replane(r, domain, eb))
    assert_bitwise(traced[(("variant", "ring"),)],
                   _hand_stencil_ring(r, domain, eb))
    # y-tiled: double refs + ring scratch, traced from the kernel
    ty = 8
    Z, Y, X = domain
    Xp, Zp = X + 2 * r, Z + 2 * r
    fl = float(6 * r + 1) * 2.0
    hand = PallasKernelSpec(
        name=f"star{r}_ytile{ty}", grid=(Y // ty, Zp),
        operands=(
            OperandSpec("src_a", (1, ty, Xp), eb, grid_deps=(0, 1)),
            OperandSpec("src_b", (1, ty, Xp), eb, grid_deps=(0, 1)),
            OperandSpec("dst", (1, ty, X), eb, grid_deps=(0, 1),
                        is_output=True),
        ),
        vpu_elems_per_step=fl * ty * X * Z / Zp, vpu_shape=(ty, X),
        scratch_bytes=(2 * r + 1) * 2 * ty * Xp * eb,
        work_per_step=float(ty * X) * Z / Zp, elem_bytes=eb)
    assert_bitwise(traced[(("ty", ty), ("variant", "ytile_ring"))], hand)


def test_lbm_traced_matches_handwritten():
    from repro.kernels.lbm_d3q15.generator import FLOPS_PER_LUP, candidate_specs

    domain, eb = (8, 16, 32), 4
    Z, Y, X = domain
    Xp = X + 2
    traced = {tuple(sorted(c.items())): s
              for c, s in candidate_specs(domain, eb)}
    # replane reads the unpadded planes and keeps 17 zero-bordered copies
    # of them, interior at the (8, 128) tile, in VMEM
    ops = tuple(
        OperandSpec(f"pdf{q}", (1, 1, Y, X), eb, grid_deps=(0,))
        for q in range(15)
    ) + tuple(
        OperandSpec(f"phase{k}", (1, Y, X), eb, grid_deps=(0,))
        for k in range(3)
    ) + (
        OperandSpec("dst", (15, 1, Y, X), eb, grid_deps=(0,), is_output=True),
    )
    hand = PallasKernelSpec(
        name="lbm_replane", grid=(Z,), operands=ops,
        vpu_elems_per_step=float(FLOPS_PER_LUP * Y * X), vpu_shape=(Y, X),
        scratch_bytes=17 * (Y + 16) * (X + 256) * eb,
        work_per_step=float(Y * X), elem_bytes=eb)
    assert_bitwise(traced[(("variant", "replane"),)], hand)
    ty = 8
    ops_t = tuple(
        OperandSpec(f"pdf{q}_{dj}", (1, 1, ty, Xp), eb, grid_deps=(0, 1))
        for dj in (0, 1) for q in range(15)
    ) + tuple(
        OperandSpec(f"phase{k}_{dj}", (1, ty, Xp), eb, grid_deps=(0, 1))
        for k in range(3) for dj in (0, 1)
    ) + (
        OperandSpec("dst", (15, 1, ty, X), eb, grid_deps=(0, 1),
                    is_output=True),
    )
    hand_t = PallasKernelSpec(
        name=f"lbm_ytile{ty}", grid=(Y // ty, Z), operands=ops_t,
        vpu_elems_per_step=float(FLOPS_PER_LUP * ty * X), vpu_shape=(ty, X),
        work_per_step=float(ty * X), elem_bytes=eb)
    assert_bitwise(traced[(("ty", ty), ("variant", "ytile"))], hand_t)


def test_matmul_traced_matches_handwritten():
    from repro.kernels.matmul.generator import candidate_specs

    M = K = N = 512
    eb = 2
    traced = {(c["bm"], c["bk"], c["bn"]): s
              for c, s in candidate_specs(M, K, N, eb)}
    for (bm, bk, bn), spec in traced.items():
        hand = PallasKernelSpec(
            name=f"mm_{bm}x{bk}x{bn}", grid=(M // bm, N // bn, K // bk),
            operands=(
                OperandSpec("a", (bm, bk), eb, grid_deps=(0, 2)),
                OperandSpec("b", (bk, bn), eb, grid_deps=(1, 2)),
                OperandSpec("o", (bm, bn), eb, grid_deps=(0, 1),
                            is_output=True),
            ),
            matmuls_per_step=(MatmulShape(bm, bk, bn),),
            scratch_bytes=bm * bn * 4,
            work_per_step=2.0 * bm * bk * bn, elem_bytes=eb)
        assert_bitwise(spec, hand)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_traced_matches_handwritten(causal):
    from repro.kernels.flash_attention.generator import candidate_specs

    B, Hq, Hkv, Sq, Skv, D, eb = 2, 8, 2, 512, 512, 64, 2
    tri = 0.5 if causal else 1.0
    traced = {(c["bq"], c["bk"]): s
              for c, s in candidate_specs(B, Hq, Hkv, Sq, Skv, D, causal, eb)}
    for (bq, bk), spec in traced.items():
        hand = PallasKernelSpec(
            name=f"fa_{bq}x{bk}", grid=(B * Hq, Sq // bq, Skv // bk),
            operands=(
                OperandSpec("q", (1, 1, bq, D), eb, grid_deps=(0, 1)),
                OperandSpec("k", (1, 1, bk, D), eb, grid_deps=(0, 2)),
                OperandSpec("v", (1, 1, bk, D), eb, grid_deps=(0, 2)),
                OperandSpec("o", (1, 1, bq, D), eb, grid_deps=(0, 1),
                            is_output=True),
            ),
            matmuls_per_step=(MatmulShape(bq, D, bk), MatmulShape(bq, bk, D)),
            vpu_elems_per_step=6.0 * bq * bk * tri, vpu_shape=(bq, bk),
            scratch_bytes=(bq * D + 2 * bq * 128) * 4,
            work_per_step=float(bq * bk) * tri, elem_bytes=eb)
        assert_bitwise(spec, hand)


# --------------------------------------------------------------------------
# traced GPU lowering vs the paper's hand specs
# --------------------------------------------------------------------------
def test_gpu_lowering_star_stencil_exact():
    from repro.kernels.stencil3d25.generator import traced_gpu_spec

    for r, domain in ((4, (32, 64, 96)), (2, (8, 16, 24))):
        assert traced_gpu_spec(r, domain, 8) == \
            specs.star_stencil_3d(r, domain, 8)


def test_gpu_lowering_gemm_exact():
    from repro.kernels.matmul.generator import traced_gpu_spec

    assert traced_gpu_spec(512, 1024, 256, 2) == \
        specs.matmul_naive(512, 1024, 256, 2)


def test_gpu_lowering_jacobi_is_2d5pt():
    from repro.kernels.jacobi2d.generator import traced_gpu_spec

    assert traced_gpu_spec((4096, 4096), 8, name="stencil2d5pt") == \
        specs.stencil_2d5pt((4096, 4096), 8)


def test_gpu_lowering_transpose_dim_map():
    from repro.kernels.transpose_pad.generator import traced_gpu_spec

    spec = traced_gpu_spec((256, 512), 4)
    assert spec.domain == (512, 256)        # out shape (N, M)
    load, store = spec.accesses
    assert not load.is_store and store.is_store
    assert load.dim_map == (1, 0)           # in[p1, p0]
    assert store.dim_map == (0, 1)


# --------------------------------------------------------------------------
# traced-only kernels: numerics + end-to-end pricing
# --------------------------------------------------------------------------
@pytest.mark.parametrize("cfg", [{"variant": "rowstream"},
                                 {"variant": "ytile", "ty": 8}])
def test_jacobi_numerics(cfg):
    from repro.kernels.jacobi2d.ops import jacobi_ref, jacobi_step

    src = jax.random.normal(jax.random.PRNGKey(0), (16, 32))
    out = jacobi_step(src, config=cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(jacobi_ref(src)),
                               atol=1e-5)


@pytest.mark.parametrize("shape,cfg", [((40, 56), {"bm": 8, "bn": 8}),
                                       ((64, 32), {"bm": 16, "bn": 32})])
def test_transpose_numerics(shape, cfg):
    from repro.kernels.transpose_pad.ops import transpose

    x = jax.random.normal(jax.random.PRNGKey(1), shape)
    np.testing.assert_allclose(np.asarray(transpose(x, config=cfg)),
                               np.asarray(x).T)


def test_traced_kernels_price_on_all_machines():
    from repro.kernels.jacobi2d.generator import (
        candidate_specs as jac_cands,
        traced_gpu_spec as jac_gpu,
    )
    from repro.kernels.transpose_pad.generator import (
        candidate_specs as tr_cands,
        traced_gpu_spec as tr_gpu,
    )

    report = Explorer().explore(
        [
            Workload("jacobi2d", gpu_spec=jac_gpu((256, 256), 8),
                     tpu_candidates=list(jac_cands((256, 256), 8))),
            Workload("transpose", gpu_spec=tr_gpu((512, 1024), 4),
                     tpu_candidates=list(tr_cands((512, 1024), 4))),
        ],
        [V100, A100, TPU_V5E],
    )
    for w in ("jacobi2d", "transpose"):
        for m in (V100.name, A100.name, TPU_V5E.name):
            assert report.best(w, m) is not None, (w, m)
    # the estimator sees transpose as pure data movement
    best = report.best("transpose", TPU_V5E.name)
    assert best.estimate.mxu_time == 0.0 and best.limiter == "HBM"


def test_price_kernel_quickstart():
    from repro.kernels.jacobi2d.kernel import make_rowstream

    report = price_kernel(
        make_rowstream((64, 128), (0.5, 0.125)),
        [arg("src", (66, 130))],
        machines=[V100, TPU_V5E],
        name="my_jacobi",
    )
    assert report.best("my_jacobi", TPU_V5E.name) is not None
    assert report.best("my_jacobi", V100.name) is not None


def test_grid_space_order():
    space = list(grid_space(bm=[1, 2], bn=[3]))
    assert space == [{"bm": 1, "bn": 3}, {"bm": 2, "bn": 3}]
    assert list(space[0]) == ["bm", "bn"]


# --------------------------------------------------------------------------
# property test: random affine index maps round-trip through the tracer
# --------------------------------------------------------------------------
@settings(max_examples=30, deadline=None)
@given(st.data())
def test_random_affine_index_map_roundtrip(data):
    ngrid = data.draw(st.integers(min_value=1, max_value=3))
    grid = tuple(data.draw(st.integers(min_value=1, max_value=4))
                 for _ in range(ngrid))
    ndim = data.draw(st.integers(min_value=1, max_value=3))
    block = tuple(data.draw(st.sampled_from([1, 2, 4]))
                  for _ in range(ndim))
    # one affine coordinate expression per block dim
    coeffs = [
        tuple(data.draw(st.integers(min_value=0, max_value=3))
              for _ in range(ngrid))
        for _ in range(ndim)
    ]
    offs = [data.draw(st.integers(min_value=0, max_value=5))
            for _ in range(ndim)]

    def index_map(*g):
        return tuple(
            sum(c * gi for c, gi in zip(cs, g)) + o
            for cs, o in zip(coeffs, offs)
        )

    arr_shape = tuple(
        b * (max((sum(c * (g - 1) for c, g in zip(cs, grid)) + o + 1), 1))
        for b, cs, o in zip(block, coeffs, offs)
    )
    traced_spec = _trace_copy_kernel(grid, block, index_map, arr_shape)
    x_op = traced_spec.operands[0]
    expected_deps = tuple(sorted(
        d for d in range(ngrid) if any(cs[d] for cs in coeffs)))
    assert x_op.grid_deps == expected_deps
    assert x_op.block_shape == block
    # fetch structure: the traced operand's count == explicit grid walk;
    # the closed form over traced deps stands unless two grid dims step
    # one block coordinate
    walked = fetch_count_oracle(grid, index_map)
    assert hbm_traffic(traced_spec)[1]["x"]["fetches"] == walked
    closed = fetch_count(grid, x_op.grid_deps)
    assert x_op.fetches == (None if closed == walked else walked)
    # volumes/footprints: traced spec == direct construction
    direct = PallasKernelSpec(
        name=traced_spec.name, grid=grid,
        operands=(
            OperandSpec("x", block, 4, grid_deps=expected_deps,
                        fetches=None if closed == walked else walked),
            traced_spec.operands[1],
        ),
        work_per_step=traced_spec.work_per_step,
        elem_bytes=4)
    assert hbm_traffic(traced_spec)[0] == hbm_traffic(direct)[0]
    et, ed = estimate_pallas(traced_spec), estimate_pallas(direct)
    for f in _EST_FIELDS:
        assert getattr(et, f) == getattr(ed, f), f


def _trace_copy_kernel(grid, block, index_map, arr_shape):
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def call(x):
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[pl.BlockSpec(block, index_map)],
            out_specs=pl.BlockSpec(block, lambda *g: (0,) * len(block)),
            out_shape=jax.ShapeDtypeStruct(block, jnp.float32),
            interpret=True,
        )(x)

    traced = trace_kernel(call, [arg("x", arr_shape)], name="copy")
    return lower_tpu(traced, CostModel(elem_bytes=4))


def test_body_negative_indices_normalize():
    """numpy-style negative ref indices/slice bounds trace like Pallas
    interpret mode executes them."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[-1, :-1] * 2.0

    def call(x):
        return pl.pallas_call(
            kernel,
            grid=(4,),
            in_specs=[pl.BlockSpec((2, 9), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((1, 8), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((4, 8), jnp.float32),
            interpret=True,
        )(x)

    traced = trace_kernel(call, [arg("x", (8, 9))], name="negidx",
                          trace_body=True, require_body=True)
    assert traced.body.ok
    load = traced.body.loads("op")[0]
    assert load.offsets == (1, 0) and load.extents == (1, 8)


def test_body_value_slice_narrows_the_load():
    """A static slice of a loaded value records the narrower ref window;
    a slice of a derived value is noted as such (no per-point address)."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        rows = jnp.concatenate([x_ref[...], x_ref[...]], axis=0)
        o_ref[...] = x_ref[...][1:5, 2:10] + rows[3:7, 0:8]

    def call(x):
        return pl.pallas_call(
            kernel,
            grid=(4,),
            in_specs=[pl.BlockSpec((8, 16), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((4, 8), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((16, 8), jnp.float32),
            interpret=True,
        )(x)

    traced = trace_kernel(call, [arg("x", (32, 16))], name="valueslice",
                          trace_body=True, require_body=True)
    windows = {(a.offsets, a.extents) for a in traced.body.loads("op")}
    assert ((1, 2), (4, 8)) in windows
    assert traced.body.notes


def test_body_scalar_where_on_predicate():
    """jnp.where over a symbolic predicate with scalar branches traces to a
    scalar unknown instead of crashing."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        s = jnp.where(pl.program_id(0) > 0, 1.0, 0.5)
        o_ref[...] = x_ref[...] * s

    def call(x):
        return pl.pallas_call(
            kernel,
            grid=(4,),
            in_specs=[pl.BlockSpec((8, 8), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 8), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((32, 8), jnp.float32),
            interpret=True,
        )(x)

    traced = trace_kernel(call, [arg("x", (32, 8))], name="scalarwhere",
                          trace_body=True, require_body=True)
    assert traced.body.ok


def test_dtype_for_rejects_unknown_sizes():
    from repro.kernels import dtype_for

    assert dtype_for(4) == jnp.float32
    with pytest.raises(ValueError, match="elem_bytes"):
        dtype_for(3)
