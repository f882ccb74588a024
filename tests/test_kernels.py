"""Pallas kernel vs pure-jnp oracle: shape/dtype sweeps + gradient checks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hrr
from repro.kernels import ops as kops
from repro.kernels import ref as kref

jax.config.update("jax_enable_x64", False)


def _data(G, R, D, dtype, seed=0):
    kz, kk = jax.random.split(jax.random.PRNGKey(seed))
    Z = jax.random.normal(kz, (G, R, D), jnp.float32).astype(dtype)
    K = hrr.generate_keys(kk, R, D, dtype)
    return Z, K


SHAPES = [
    (1, 1, 64),
    (2, 2, 128),
    (4, 4, 128),
    (8, 2, 256),
    (3, 5, 96),     # non-power-of-two D, G not multiple of GT tile target
    (16, 16, 128),
    (2, 8, 512),
    # prefill chunks of 1, 2 and 4 slot groups (256 rows each) at R=4: the
    # row block grows past 8 rows, up to ROW_BLOCK
    (256, 4, 512),
    (512, 4, 512),
    (1024, 4, 512),
]


@pytest.mark.parametrize("G,R,D", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bind_kernel_matches_ref(G, R, D, dtype):
    Z, K = _data(G, R, D, dtype)
    got = kops.bind_superpose_pallas(Z, K)
    want = kref.bind_superpose_ref(Z.astype(jnp.float32), K.astype(jnp.float32))
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), rtol=tol, atol=tol)
    assert got.dtype == dtype and got.shape == (G, D)


@pytest.mark.parametrize("G,R,D", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_unbind_kernel_matches_ref(G, R, D, dtype):
    Z, K = _data(G, R, D, dtype)
    S = kref.bind_superpose_ref(Z.astype(jnp.float32), K.astype(jnp.float32)).astype(dtype)
    got = kops.unbind_pallas(S, K)
    want = kref.unbind_ref(S.astype(jnp.float32), K.astype(jnp.float32))
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), rtol=tol, atol=tol)
    assert got.shape == (G, R, D)


@pytest.mark.parametrize("op", ["bind", "unbind"])
def test_kernels_match_ref_at_model_width(op):
    """One prefill chunk (256 rows, R=4) at deepseek-7b's D=4096, float32:
    a grid of 32 x 32 tiles, each key block serving all 256 rows.  (A
    bfloat16 output accumulates over the 32 j-tiles in bfloat16, which
    drifts past 5e-2 at this width.)"""
    Z, K = _data(256, 4, 4096, jnp.float32)
    S = kref.bind_superpose_ref(Z, K)
    if op == "bind":
        got, want = kops.bind_superpose_pallas(Z, K), S
    else:
        got, want = kops.unbind_pallas(S, K), kref.unbind_ref(S, K)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["fft", "direct"])
def test_jnp_backends_match_ref(backend):
    Z, K = _data(4, 4, 128, jnp.float32)
    S = hrr.bind_superpose(Z, K, backend=backend)
    np.testing.assert_allclose(np.asarray(S), np.asarray(kref.bind_superpose_ref(Z, K)),
                               rtol=2e-4, atol=2e-4)
    Zh = hrr.unbind(S, K, backend=backend)
    np.testing.assert_allclose(np.asarray(Zh), np.asarray(kref.unbind_ref(S, K)),
                               rtol=2e-4, atol=2e-4)


def test_bind_custom_vjp_matches_autodiff_of_ref():
    Z, K = _data(2, 4, 128, jnp.float32)
    dS = jax.random.normal(jax.random.PRNGKey(7), (2, 128))

    def f_pallas(z):
        return jnp.vdot(kops.bind_superpose_pallas(z, K), dS)

    def f_ref(z):
        return jnp.vdot(kref.bind_superpose_ref(z, K), dS)

    gp = jax.grad(f_pallas)(Z)
    gr = jax.grad(f_ref)(Z)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr), rtol=1e-4, atol=1e-4)


def test_unbind_custom_vjp_matches_autodiff_of_ref():
    Z, K = _data(2, 4, 128, jnp.float32)
    S = kref.bind_superpose_ref(Z, K)
    dZ = jax.random.normal(jax.random.PRNGKey(8), (2, 4, 128))

    def f_pallas(s):
        return jnp.vdot(kops.unbind_pallas(s, K), dZ)

    def f_ref(s):
        return jnp.vdot(kref.unbind_ref(s, K), dZ)

    gp = jax.grad(f_pallas)(S)
    gr = jax.grad(f_ref)(S)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr), rtol=1e-4, atol=1e-4)


def test_keys_get_no_gradient():
    Z, K = _data(2, 2, 128, jnp.float32)
    g = jax.grad(lambda k: kops.bind_superpose_pallas(Z, k).sum())(K)
    np.testing.assert_array_equal(np.asarray(g), 0.0)


@pytest.mark.parametrize("G,GT", [(1, 1), (3, 3), (4, 4), (12, 12),
                                  (8, 8), (24, 24), (40, 40), (264, 88),
                                  (256, 256), (512, 256), (1024, 256)])
def test_row_block_follows_from_G(G, GT):
    """Decode's G = slots/R = 4 and odd G keep GT = G; a multiple of 8
    takes the largest multiple of 8 dividing it, up to ROW_BLOCK."""
    from repro.kernels import circconv
    D = 4096
    T, gt, grid = circconv._geometry(G, D)
    assert (T, gt) == (128, GT)
    assert grid == (G // GT, D // T, D // T) and G % GT == 0


def test_row_block_divides_every_G():
    from repro.kernels import circconv
    for G in range(1, 2049):
        GT = circconv._row_block(G)
        assert G % GT == 0
        if G % 8:
            assert GT == G
        else:
            assert GT % 8 == 0 and GT <= circconv.ROW_BLOCK


# ---------------------------------------------------------------------------
# degenerate tile shapes: non-MXU-alignable D must fail loudly, not run a
# T=1 Toeplitz grid (the _pick_tile degradation bug)
# ---------------------------------------------------------------------------

def test_mxu_alignable_classifier():
    from repro.kernels import circconv
    for D in (64, 96, 128, 256, 512, 1024, 4096):
        assert circconv.mxu_alignable(D), D
    # 4097 = 17 x 241: largest divisor <= 128 is 17, not 8-aligned
    for D in (4097, 127, 241):
        assert not circconv.mxu_alignable(D), D


@pytest.mark.parametrize("op", ["bind", "unbind"])
def test_kernel_raises_on_degenerate_tile_D4097(op):
    """Direct kernel calls with D=4097 (prime-ish: tile degrades to 17)
    must raise a clear error instead of silently running a 17x17-tile
    grid slower than backend='direct'."""
    from repro.kernels import circconv
    D = 4097
    Z = jnp.zeros((1, 2, D), jnp.float32)
    K = jnp.zeros((2, 2 * D), jnp.float32)
    with pytest.raises(ValueError, match="not MXU-alignable"):
        if op == "bind":
            circconv.bind_superpose_kernel(Z, K)
        else:
            circconv.unbind_kernel(jnp.zeros((1, D)), K)


def test_hrr_pallas_falls_back_to_fft_for_degenerate_D():
    """The high-level hrr entry points reroute pallas -> fft for
    non-alignable D — with a warning (loud), and values equal to the fft
    backend (the reroute really is the fft path, not a broken kernel)."""
    Z, K = _data(2, 2, 127, jnp.float32)
    with pytest.warns(UserWarning, match="falling back to the fft backend"):
        S = hrr.bind_superpose(Z, K, backend="pallas")
    np.testing.assert_array_equal(
        np.asarray(S), np.asarray(hrr.bind_superpose(Z, K, backend="fft")))
    with pytest.warns(UserWarning, match="falling back to the fft backend"):
        Zh = hrr.unbind(S, K, backend="pallas")
    np.testing.assert_array_equal(
        np.asarray(Zh), np.asarray(hrr.unbind(S, K, backend="fft")))


def test_alignable_pallas_does_not_warn():
    Z, K = _data(2, 2, 128, jnp.float32)
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        kops.bind_superpose_pallas(Z, K)
        hrr.bind_superpose(Z, K, backend="pallas")


# ---------------------------------------------------------------------------
# effective execution mode: spec() stays canonical, execution_mode() tells
# the truth (the silent interpret-mode bug)
# ---------------------------------------------------------------------------

def test_circconv_execution_mode_matches_host():
    from repro.kernels import circconv
    mode = circconv.execution_mode()
    if jax.default_backend() == "tpu":
        assert mode == "pallas-compiled"
    else:
        assert mode == "pallas-interpret"
    assert circconv.interpret_mode() == (mode == "pallas-interpret")


def test_codec_execution_mode_vs_spec():
    from repro.codecs import build
    c = build("c3sl:R=2,backend=pallas", D=256)
    # spec stays the canonical registry string regardless of host
    assert "backend=pallas" in c.spec()
    assert c.execution_mode() in ("pallas-compiled", "pallas-interpret")
    assert build("c3sl:R=2,backend=fft", D=256).execution_mode() == "fft"
    assert build("c3sl:R=2,backend=direct", D=256).execution_mode() == "direct"
    # degenerate D: the pallas spec executes as fft — and says so
    assert build("c3sl:R=2,backend=pallas",
                 D=4097).execution_mode() == "fft-fallback"
