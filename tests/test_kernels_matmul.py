"""Interpret-mode allclose sweeps: Pallas MX/baseline matmul vs jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.baseline_matmul import baseline_matmul
from repro.kernels.mx_matmul import mx_matmul
from repro.kernels.ref import baseline_matmul_ref, matmul_bias_ref, matmul_ref

SHAPES = [
    (32, 32, 32),
    (64, 128, 96),
    (96, 160, 224),   # non-square
    (33, 65, 17),     # ragged (exercises padding)
    (256, 64, 128),
]
BLOCKS = [(32, 32, 32), (16, 64, 32), (64, 32, 16)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", SHAPES, ids=str)
@pytest.mark.parametrize("blocks", BLOCKS, ids=str)
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_mx_matmul_matches_oracle(shape, blocks, dtype):
    M, K, N = shape
    bm, bn, bk = blocks
    a = jax.random.normal(jax.random.PRNGKey(0), (M, K)).astype(dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (K, N)).astype(dtype)
    got = mx_matmul(a, b, bm=bm, bn=bn, bk=bk, interpret=True, out_dtype=jnp.float32)
    want = matmul_ref(a, b, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_tol(dtype))


@pytest.mark.parametrize("shape", SHAPES[:3], ids=str)
def test_mx_matmul_bias(shape):
    M, K, N = shape
    a = jax.random.normal(jax.random.PRNGKey(0), (M, K))
    b = jax.random.normal(jax.random.PRNGKey(1), (K, N))
    c = jax.random.normal(jax.random.PRNGKey(2), (M, N))
    got = mx_matmul(a, b, c, bm=32, bn=32, bk=32, interpret=True)
    want = matmul_bias_ref(a, b, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", SHAPES[:4], ids=str)
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_baseline_matmul_matches_oracle(shape, dtype):
    """Baseline accumulates through the output buffer in out dtype: compare
    against the chunked-accumulation oracle (not plain matmul) for bf16."""
    M, K, N = shape
    bk = 32
    a = jax.random.normal(jax.random.PRNGKey(0), (M, K)).astype(dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (K, N)).astype(dtype)
    got = baseline_matmul(a, b, bm=32, bn=32, bk=bk, interpret=True)
    want = baseline_matmul_ref(a, b, bk=bk)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


def test_mx_beats_baseline_accumulation_precision():
    """The MX f32 accumulator (the near-FPU buffer) gives strictly better
    bf16 numerics than the baseline's in-dtype round-tripping — a real
    correctness dividend of the paper's design."""
    M = K = N = 512
    a = (jax.random.normal(jax.random.PRNGKey(0), (M, K)) * 0.5).astype(jnp.bfloat16)
    b = (jax.random.normal(jax.random.PRNGKey(1), (K, N)) * 0.5).astype(jnp.bfloat16)
    exact = matmul_ref(a, b, out_dtype=jnp.float32)
    mx = mx_matmul(a, b, bm=128, bn=128, bk=64, interpret=True).astype(jnp.float32)
    base = baseline_matmul(a, b, bm=128, bn=128, bk=64, interpret=True).astype(jnp.float32)
    err_mx = float(jnp.abs(mx - exact).mean())
    err_base = float(jnp.abs(base - exact).mean())
    assert err_mx < err_base


def test_policy_dispatch():
    from repro.core.ops import MXPolicy, matmul, use_policy

    a = jax.random.normal(jax.random.PRNGKey(0), (64, 48))
    b = jax.random.normal(jax.random.PRNGKey(1), (48, 96))
    want = matmul_ref(a, b)
    for backend in ("xla", "pallas_mx", "pallas_baseline"):
        with use_policy(MXPolicy(backend=backend, bm=32, bn=32, bk=16, interpret=True)):
            got = matmul(a, b)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_policy_batched_lhs():
    from repro.core.ops import MXPolicy, matmul, use_policy

    a = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 48))
    b = jax.random.normal(jax.random.PRNGKey(1), (48, 32))
    with use_policy(MXPolicy(backend="pallas_mx", bm=16, bn=32, bk=16, interpret=True)):
        got = matmul(a, b)
    want = jnp.einsum("bmk,kn->bmn", a, b)
    assert got.shape == (2, 8, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend", ["xla", "pallas_mx", "pallas_baseline"])
def test_policy_dispatch_transposed_weights(backend):
    """The tied LM head: weights stored (N, K) — N ragged against bn, so the
    kernel's last column block runs past N — through every backend."""
    from repro.core.ops import MXPolicy, matmul, use_policy

    a = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 48))
    table = jax.random.normal(jax.random.PRNGKey(1), (100, 48))
    with use_policy(MXPolicy(backend=backend, bm=16, bn=32, bk=16)):
        got = matmul(a, table, out_dtype=jnp.float32, b_transposed=True)
    want = jnp.einsum("bmk,nk->bmn", a, table)
    assert got.shape == (2, 8, 100)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_default_policy_follows_platform(monkeypatch):
    """No flag picks the kernels: the XLA path (and the interpreter for an
    explicit Pallas policy) off-TPU; compiled Pallas on a TPU, which
    refuses interpret mode."""
    from repro.core.ops import MXPolicy

    assert (MXPolicy().backend, MXPolicy().interpret) == ("xla", True)
    assert MXPolicy(backend="pallas_mx").interpret is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert (MXPolicy().backend, MXPolicy().interpret) == ("pallas_mx", False)
    with pytest.raises(ValueError, match="interpret"):
        MXPolicy(backend="pallas_mx", interpret=True)
