"""Pallas-kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret mode executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

# interpret-mode kernel sweeps are CPU-heavy; deselected in quick CI
pytestmark = pytest.mark.slow


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,S,hd", [
    (1, 2, 2, 128, 64),      # MHA
    (2, 4, 2, 256, 64),      # GQA 2:1
    (1, 8, 1, 128, 128),     # MQA
])
@pytest.mark.parametrize("window", [None, 128])
def test_flash_attention_sweep(B, H, KV, S, hd, dtype, window):
    key = jax.random.PRNGKey(0)
    q = (jax.random.normal(key, (B, H, S, hd)) * 0.3).astype(dtype)
    k = (jax.random.normal(jax.random.fold_in(key, 1), (B, KV, S, hd)) * 0.3).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, KV, S, hd)).astype(dtype)
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              block_q=64, block_k=64)
    exp = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


def test_flash_attention_noncausal():
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (1, 2, 128, 64)) * 0.3
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 256, 64)) * 0.3
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 256, 64))
    out = ops.flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    exp = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,L,H,K,chunk", [
    (1, 64, 2, 32, 32),
    (2, 128, 4, 64, 64),
])
def test_rwkv6_scan_sweep(B, L, H, K, chunk):
    key = jax.random.PRNGKey(2)
    r = jax.random.normal(key, (B, L, H, K)) * 0.5
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, L, H, K)) * 0.5
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, L, H, K))
    logw = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 3),
                                      (B, L, H, K)) * 0.5 - 0.5)
    u = jax.random.normal(jax.random.fold_in(key, 4), (H, K)) * 0.3
    out = ops.rwkv6_scan(r, k, v, logw, u, chunk=chunk)
    exp, _ = ref.rwkv6_ref(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-3, atol=1e-3)


def test_rwkv6_strong_decay_stable():
    """Strong decay (log w << 0) must not overflow the chunked form."""
    key = jax.random.PRNGKey(3)
    B, L, H, K = 1, 128, 2, 32
    r = jax.random.normal(key, (B, L, H, K)) * 0.5
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, L, H, K)) * 0.5
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, L, H, K))
    logw = jnp.full((B, L, H, K), -8.0)   # near-total forgetting
    u = jnp.zeros((H, K))
    out = ops.rwkv6_scan(r, k, v, logw, u, chunk=64)
    exp, _ = ref.rwkv6_ref(r, k, v, logw, u)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,T,D,N,chunk,bd", [
    (1, 64, 128, 8, 32, 128),
    (2, 128, 256, 16, 64, 128),
])
def test_mamba_scan_sweep(B, T, D, N, chunk, bd):
    key = jax.random.PRNGKey(4)
    dt = jax.nn.softplus(jax.random.normal(key, (B, T, D)) - 1)
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 1), (D, N)) * 0.3)
    Bt = jax.random.normal(jax.random.fold_in(key, 2), (B, T, N)) * 0.5
    Ct = jax.random.normal(jax.random.fold_in(key, 3), (B, T, N)) * 0.5
    x = jax.random.normal(jax.random.fold_in(key, 4), (B, T, D))
    y = ops.mamba_scan(dt, A, Bt, Ct, x, chunk=chunk, block_d=bd)
    ye, _ = ref.mamba_scan_ref(dt, A, Bt, Ct, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("N,block", [(1024, 256), (4096, 1024)])
def test_waterfill_sweep(N, block):
    key = jax.random.PRNGKey(5)
    j = jnp.abs(jax.random.normal(key, (N,))) * 1e-3 + 1e-5
    rmin = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (N,))) * 1e5
    mu = jnp.logspace(-6, 1, 16)
    # impl="interpret" keeps the kernel body under test ("auto" routes to the
    # ref oracle on CPU, which would compare the oracle against itself)
    g1 = ops.waterfill_gprime(mu, j, rmin, 20e6, block_n=block,
                              impl="interpret")
    g2 = ref.waterfill_gprime_ref(mu, j, rmin, 20e6)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-4,
                               atol=1.0)


def test_kernel_mode_resolution(monkeypatch):
    """"auto" is the ref oracle off a TPU unless interpret mode is asked
    for; an explicit mode is kept as given; anything else is an error."""
    monkeypatch.delenv("REPRO_FORCE_INTERPRET", raising=False)
    assert ops.kernel_mode() == ("mosaic" if jax.default_backend() == "tpu"
                                 else "ref")
    for mode in ops.KERNEL_MODES:
        assert ops.kernel_mode(mode) == mode
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
    assert ops.kernel_mode() == "interpret"
    assert ops.kernel_mode("mosaic") == "mosaic"
    with pytest.raises(ValueError, match="impl"):
        ops.kernel_mode("pallas")


def test_model_chunked_attention_matches_ref():
    """The XLA-path chunked attention in models/ must agree with the oracle."""
    from repro.models.attention import _chunked_attn
    key = jax.random.PRNGKey(6)
    B, S, H, KV, hd = 2, 256, 4, 2, 64
    q = jax.random.normal(key, (B, S, H, hd)) * 0.3
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, hd)) * 0.3
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, hd))
    out = _chunked_attn(q, k, v, causal=True, window=64, scale=hd ** -0.5,
                        chunk=128)
    # oracle works in (B,H,S,hd) layout
    exp = ref.flash_attention_ref(q.transpose(0, 2, 1, 3),
                                  k.transpose(0, 2, 1, 3),
                                  v.transpose(0, 2, 1, 3),
                                  causal=True, window=64)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(exp.transpose(0, 2, 1, 3)),
                               rtol=2e-5, atol=2e-5)
