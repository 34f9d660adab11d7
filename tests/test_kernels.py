"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import make_flash_attention, make_flash_decode
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.lbm_d3q15.generator import _space as lbm_space
from repro.kernels.lbm_d3q15.kernel import make_kernel as make_lbm
from repro.kernels.lbm_d3q15.ref import WEIGHTS, lbm_step_ref, pad_inputs
from repro.kernels.matmul.kernel import make_matmul
from repro.kernels.stencil3d25.kernel import make_kernel as make_stencil
from repro.kernels.stencil3d25.ref import pad_input, star_stencil_ref, star_weights


@pytest.mark.parametrize("r", [1, 2, 4])
@pytest.mark.parametrize("variant,ty,dom", [
    pytest.param("replane", None, (5, 16, 24), id="replane-None"),
    pytest.param("ring", None, (5, 16, 24), id="ring-None"),
    pytest.param("ring", None, (12, 16, 128), id="ring-None-12x16x128"),
    pytest.param("ytile_ring", 8, (5, 16, 24), id="ytile_ring-8")])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_stencil_variants(r, variant, ty, dom, dtype):
    """ring takes the source unpadded (Z = 5 < 2r covers its clamped
    source block and zero planes); the others take it zero-padded."""
    Z, Y, X = dom
    src = jax.random.normal(jax.random.PRNGKey(r), (Z, Y, X), dtype=dtype)
    w = star_weights(r, dtype)
    ref = star_stencil_ref(pad_input(src, r), w, r)
    arg = src if variant == "ring" else pad_input(src, r)
    if variant == "ytile_ring":
        if ty < 2 * r:
            pytest.skip("ty < 2r")
        ny = Y // ty
        extra = (ny + 1) * ty - (Y + 2 * r)
        arg = jnp.pad(arg, ((0, 0), (0, extra), (0, 0)))
    k = make_stencil(variant, r, (Z, Y, X), tuple(float(x) for x in w), dtype, ty)
    out = k(arg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("config,pads", [
    ({"variant": "ring"}, False), ({"variant": "ytile_ring", "ty": 8}, True)])
def test_stencil_pads_in_hbm_only_for_padded_variants(config, pads):
    """ring keeps its zero halo in VMEM: its program has no pad op."""
    from repro.kernels.stencil3d25.ops import star_stencil

    src = jax.ShapeDtypeStruct((12, 16, 128), jnp.float32)
    w = (1.0 / 25,) * 25
    hlo = jax.jit(lambda s: star_stencil(s, w, r=4, config=config)) \
        .lower(src).as_text()
    assert ("stablehlo.pad" in hlo) == pads


@pytest.mark.parametrize("config,pads", [
    ({"variant": "replane"}, False), ({"variant": "ytile", "ty": 8}, True)])
def test_lbm_pads_in_hbm_only_for_ytile(config, pads):
    """replane makes its zero halo in VMEM: its program has no pad op."""
    from repro.kernels.lbm_d3q15.ops import lbm_step

    dom = (8, 32, 128)
    pdf = jax.ShapeDtypeStruct((15, *dom), jnp.float32)
    phase = jax.ShapeDtypeStruct(dom, jnp.float32)
    hlo = jax.jit(lambda p, f: lbm_step(p, f, config=config)) \
        .lower(pdf, phase).as_text()
    assert ("stablehlo.pad" in hlo) == pads


# Z = 1 puts every cz = +-1 pull in the z-halo; (3, 16, 256) has Y != X
@pytest.mark.parametrize("dom", [(3, 8, 16), (4, 16, 8), (1, 8, 16), (3, 16, 256)])
@pytest.mark.parametrize("variant,ty", [("replane", None), ("ytile", 4)])
def test_lbm_variants(dom, variant, ty):
    """replane takes the source unpadded; ytile takes it zero-padded, y
    further down to whole tiles plus one.  The PDFs are off equilibrium
    and non-zero on every face, so a halo read as anything but zeros, or
    a PDF pulled from a wrong cell, shows."""
    Z, Y, X = dom
    kp, kn = jax.random.split(jax.random.PRNGKey(0))
    phase = jax.nn.sigmoid(jax.random.normal(kp, dom))
    noise = 1e-3 * jax.random.normal(kn, (15, *dom))
    pdf = jnp.stack([w * phase for w in WEIGHTS]) + noise
    pdf_p, ph_p = pad_inputs(pdf, phase)
    ref, _ = lbm_step_ref(pdf_p, ph_p)
    if variant == "replane":
        out = make_lbm(variant, dom)(pdf, phase)
    else:
        ny = Y // ty
        extra = (ny + 1) * ty - (Y + 2)
        pdf_p = jnp.pad(pdf_p, ((0, 0), (0, 0), (0, extra), (0, 0)))
        ph_p = jnp.pad(ph_p, ((0, 0), (0, extra), (0, 0)))
        out = make_lbm(variant, dom, ty)(pdf_p, ph_p)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


LBM_DOMAIN = (8, 32, 128)    # Mosaic-tileable planes; Y = 32 keeps two ytiles


@pytest.mark.parametrize("config", list(lbm_space(LBM_DOMAIN)),
                         ids=lambda c: c["variant"] + str(c.get("ty", "")))
def test_lbm_step_matches_the_reference(config):
    """The public ``lbm_step`` for each candidate, its own padding (ytile's
    extra y rows too) included, on PDFs off equilibrium (w_q * phase plus
    noise), against ``lbm_step_ref`` for both returned values."""
    from repro.kernels.lbm_d3q15.ops import lbm_step

    kp, kn = jax.random.split(jax.random.PRNGKey(5))
    phase = jax.random.uniform(kp, LBM_DOMAIN)
    noise = 1e-3 * jax.random.normal(kn, (15, *LBM_DOMAIN))
    pdf = jnp.asarray(WEIGHTS)[:, None, None, None] * phase + noise
    want_pdf, want_phase = lbm_step_ref(*pad_inputs(pdf, phase))
    got_pdf, got_phase = lbm_step(pdf, phase, config=config)
    # Both sides evaluate the same float32 expressions, save the normal's
    # rsqrt against ** -0.5 and the order of the phase sum: a few ulp of
    # outputs below 1 (ulp 6e-8), far under a PDF pulled from a wrong cell
    # (the noise alone differs by ~1e-3 between neighbours).
    np.testing.assert_allclose(np.asarray(got_pdf), np.asarray(want_pdf),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_phase), np.asarray(want_phase),
                               rtol=0, atol=1e-6)


@pytest.mark.parametrize("shape", [(128, 128, 128), (256, 384, 128), (128, 256, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_sweep(shape, dtype):
    M, K, N = shape
    a = jax.random.normal(jax.random.PRNGKey(0), (M, K), dtype=dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (K, N), dtype=dtype)
    out = make_matmul(M, K, N, 128, 128, 128, dtype)(a, b)
    ref = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32))
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=tol, atol=tol * 8
    )


@pytest.mark.parametrize("gqa", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(gqa, causal):
    Hq, Hkv = gqa
    B, S, D = 2, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Hq, S, D))
    k = jax.random.normal(ks[1], (B, Hkv, S, D))
    v = jax.random.normal(ks[2], (B, Hkv, S, D))
    out = make_flash_attention(B, Hq, Hkv, S, S, D, 128, 128, causal)(q, k, v)
    ref = attention_ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3)


@pytest.mark.parametrize("call,err", [
    ("matmul", RuntimeError),       # no 128-multiple blocking of 100
    ("flash", ValueError),          # S not a multiple of 128
])
def test_entry_points_raise_instead_of_substituting(call, err):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.matmul.ops import tuned_matmul

    x = jnp.ones((1, 2, 100, 64))
    with pytest.raises(err):
        if call == "matmul":
            tuned_matmul(x[0, 0], x[0, 0].T)
        else:
            flash_attention(x, x, x)


@pytest.mark.parametrize("bk", [128, 256])
def test_flash_decode(bk):
    B, Hq, Hkv, S, D = 2, 8, 2, 512, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, Hq, 1, D))
    k = jax.random.normal(ks[1], (B, Hkv, S, D))
    v = jax.random.normal(ks[2], (B, Hkv, S, D))
    out = make_flash_decode(B, Hq, Hkv, S, D, bk)(q, k, v)
    ref = attention_ref(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3)


def test_flash_bf16():
    B, Hq, Hkv, S, D = 1, 2, 2, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, Hq, S, D), dtype=jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), dtype=jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), dtype=jnp.bfloat16)
    out = make_flash_attention(B, Hq, Hkv, S, S, D, 128, 128, True, jnp.bfloat16)(q, k, v)
    ref = attention_ref(q, k, v, True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )
