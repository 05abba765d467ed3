"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.dwconv2d import dwconv2d_pallas
from repro.kernels.pwconv import pwconv_pallas

RNG = np.random.default_rng(0)


def _arr(shape, dtype=np.float32, scale=1.0):
    return jnp.asarray((RNG.normal(size=shape) * scale).astype(dtype))


# ---------------------------------------------------------------------------
# dwconv2d
# ---------------------------------------------------------------------------

DW2D_CASES = [
    # (B, Hi, Wi, C, Hf, Wf, stride)
    (1, 8, 8, 4, 3, 3, 1),
    (2, 12, 9, 16, 3, 3, 2),
    (1, 16, 16, 32, 5, 5, 1),
    (2, 19, 23, 40, 3, 3, 2),
    (1, 7, 7, 130, 3, 3, 1),     # channel padding path (>128 lanes)
    (1, 14, 14, 8, 5, 5, 2),
]


@pytest.mark.parametrize("b,hi,wi,c,hf,wf,s", DW2D_CASES)
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_dwconv2d_matches_ref(b, hi, wi, c, hf, wf, s, dtype):
    x = _arr((b, hi, wi, c)).astype(dtype)
    f = _arr((hf, wf, c)).astype(dtype)
    got = dwconv2d_pallas(x, f, stride=s, interpret=True)
    want = ref.dwconv2d_ref(x, f, stride=s, padding="valid")
    tol = 1e-5 if dtype == np.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_dwconv2d_same_padding():
    x = _arr((2, 10, 11, 12))
    f = _arr((3, 3, 12))
    got = ops.dwconv2d(x, f, stride=1, padding="same", impl="pallas",
                       interpret=True)
    want = ref.dwconv2d_ref(x, f, stride=1, padding="same")
    assert got.shape == x.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_dwconv2d_ref_matches_naive_loops():
    x = RNG.normal(size=(1, 9, 8, 6)).astype(np.float32)
    f = RNG.normal(size=(3, 3, 6)).astype(np.float32)
    naive = ref.dwconv2d_loops_ref(x, f, stride=2)
    lax_ = ref.dwconv2d_ref(jnp.asarray(x), jnp.asarray(f), stride=2,
                            padding="valid")
    np.testing.assert_allclose(naive, np.asarray(lax_), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# pwconv (output-stationary GEMM)
# ---------------------------------------------------------------------------

PW_CASES = [
    (16, 16, 16, 8, 128, 128),
    (300, 200, 170, 128, 128, 64),
    (64, 256, 512, 64, 256, 128),
    (100, 100, 100, 128, 128, 128),   # all-pad path
]


@pytest.mark.parametrize("g,ci,co,bg,bco,bci", PW_CASES)
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_pwconv_matches_ref(g, ci, co, bg, bco, bci, dtype):
    x = _arr((g, ci)).astype(dtype)
    w = _arr((ci, co), scale=ci ** -0.5).astype(dtype)
    got = pwconv_pallas(x, w, block_g=bg, block_co=bco, block_ci=bci,
                        interpret=True)
    want = ref.pwconv_ref(x, w)
    tol = 1e-4 if dtype == np.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("act", [None, "relu", "relu6", "gelu", "silu"])
def test_pwconv_fused_epilogue(act):
    x = _arr((65, 48))
    w = _arr((48, 33), scale=0.1)
    bias = _arr((33,))
    got = pwconv_pallas(x, w, bias, activation=act, block_g=32,
                        block_co=128, block_ci=32, interpret=True)
    want = ref.pwconv_ref(x, w, bias=bias, activation=act)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_pwconv_nd_wrapper():
    x = _arr((2, 7, 5, 24))
    w = _arr((24, 16))
    got = ops.pwconv(x, w, impl="pallas", interpret=True, block_g=8,
                     block_co=128, block_ci=128)
    want = ref.pwconv_ref(x, w)
    assert got.shape == (2, 7, 5, 16)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_rtra_oracle_equals_matmul():
    a = _arr((45, 70))
    b = _arr((70, 31))
    np.testing.assert_allclose(ref.matmul_rtra_ref(a, b, block_k=32),
                               a @ b, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Edge geometry: block planner fallbacks, sub-128 Co padding, SAME + stride 2
# ---------------------------------------------------------------------------

from repro.kernels import blocking  # noqa: E402


def test_dwconv2d_tiny_block_execution_path():
    """The planner's power-of-two lane fallback (tests/test_blocking.py)
    must correspond to a correct kernel execution path at forced tiny
    blocks."""
    assert blocking.plan_dwconv2d(14, 14, 12, 12, 512).block_c == 512
    x = _arr((1, 9, 9, 12))
    f = _arr((3, 3, 12))
    got = dwconv2d_pallas(x, f, stride=1, block_c=2, interpret=True)
    want = ref.dwconv2d_ref(x, f, stride=1, padding="valid")
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("co", [1, 7, 33, 127])
def test_pwconv_co_smaller_than_128_padding(co):
    """Co < 128 forces lane padding of the output tile (bco=max(128,co));
    the unpadded slice must match the oracle exactly."""
    x = _arr((40, 64))
    w = _arr((64, co), scale=0.125)
    bias = _arr((co,), scale=0.1)
    got = pwconv_pallas(x, w, bias, activation="relu", interpret=True)
    want = ref.pwconv_ref(x, w, bias=bias, activation="relu")
    assert got.shape == (40, co)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("hi,wi,hf", [(11, 13, 3), (14, 14, 5), (7, 9, 3)])
def test_dwconv2d_same_padding_stride2(hi, wi, hf):
    """SAME + stride 2: odd/even spatial sizes hit asymmetric pad splits and
    the VALID-remainder crop inside the kernel wrapper."""
    c = 10
    x = _arr((2, hi, wi, c))
    f = _arr((hf, hf, c))
    got = ops.dwconv2d(x, f, stride=2, padding="same", impl="pallas",
                       interpret=True)
    want = ref.dwconv2d_ref(x, f, stride=2, padding="same")
    assert got.shape == want.shape == (2, -(-hi // 2), -(-wi // 2), c)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Property-based invariants (hypothesis)
# ---------------------------------------------------------------------------


@settings(max_examples=15, deadline=None)
@given(
    c=st.integers(1, 24),
    hf=st.sampled_from([1, 3, 5]),
    s=st.sampled_from([1, 2]),
    seed=st.integers(0, 2**31 - 1),
)
def test_dwconv2d_linearity(c, hf, s, seed):
    """DWConv is linear in the input: f(ax+by) == a f(x) + b f(y)."""
    r = np.random.default_rng(seed)
    hi = hf + 4
    x = jnp.asarray(r.normal(size=(1, hi, hi, c)).astype(np.float32))
    y = jnp.asarray(r.normal(size=(1, hi, hi, c)).astype(np.float32))
    f = jnp.asarray(r.normal(size=(hf, hf, c)).astype(np.float32))
    lhs = dwconv2d_pallas(2.0 * x + 3.0 * y, f, stride=s, interpret=True)
    rhs = (2.0 * dwconv2d_pallas(x, f, stride=s, interpret=True)
           + 3.0 * dwconv2d_pallas(y, f, stride=s, interpret=True))
    np.testing.assert_allclose(lhs, rhs, rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(
    g=st.integers(1, 40),
    ci=st.integers(1, 40),
    co=st.integers(1, 40),
    seed=st.integers(0, 2**31 - 1),
)
def test_pwconv_matches_matmul_any_shape(g, ci, co, seed):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(g, ci)).astype(np.float32))
    w = jnp.asarray(r.normal(size=(ci, co)).astype(np.float32))
    got = pwconv_pallas(x, w, block_g=16, block_co=128, block_ci=16,
                        interpret=True)
    np.testing.assert_allclose(got, x @ w, rtol=2e-4, atol=2e-4)
