"""Pallas kernel validation (deliverable c): shape/dtype sweeps, allclose vs
the pure-jnp oracles in kernels/ref.py, in interpret mode on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand(key, *shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


FLASH_CASES = [
    # (b, s, h, kv, hd, window, dtype)
    (2, 64, 4, 2, 32, 0, jnp.float32),
    (1, 128, 4, 4, 64, 0, jnp.float32),
    (2, 96, 8, 2, 80, 32, jnp.float32),    # non-128 head_dim (danube-like)
    (1, 256, 4, 1, 128, 64, jnp.float32),  # MQA + window (gemma-like)
    (1, 200, 2, 2, 48, 0, jnp.float32),    # ragged seq (padding path)
    (2, 64, 4, 2, 64, 0, jnp.bfloat16),
]


@pytest.mark.parametrize("b,s,h,kv,hd,window,dtype", FLASH_CASES)
def test_flash_attention(b, s, h, kv, hd, window, dtype):
    q = _rand(1, b, s, h, hd, dtype=dtype)
    k = _rand(2, b, s, kv, hd, dtype=dtype)
    v = _rand(3, b, s, kv, hd, dtype=dtype)
    out = ops.flash_attention(q, k, v, causal=True, window=window)
    exp = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


DECODE_CASES = [
    (2, 64, 4, 2, 32, jnp.float32),
    (1, 300, 8, 2, 80, jnp.float32),       # unpadded cache length
    (3, 1024, 4, 1, 128, jnp.float32),
    (2, 128, 4, 4, 64, jnp.bfloat16),
]


@pytest.mark.parametrize("b,L,h,kv,hd,dtype", DECODE_CASES)
def test_decode_attention(b, L, h, kv, hd, dtype):
    q = _rand(4, b, 1, h, hd, dtype=dtype)
    k = _rand(5, b, L, kv, hd, dtype=dtype)
    v = _rand(6, b, L, kv, hd, dtype=dtype)
    valid = jnp.arange(L) < (L - 7)
    out = ops.decode_attention(q, k, v, valid)
    exp = ref.decode_attention_ref(q, k, v, valid)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


SSD_CASES = [
    (2, 64, 4, 32, 16, 16),
    (1, 128, 8, 64, 32, 32),
    (2, 100, 4, 32, 16, 16),               # padded seq
    (1, 64, 2, 64, 128, 64),               # full mamba2-like state
]


@pytest.mark.parametrize("b,s,h,p,n,chunk", SSD_CASES)
def test_ssd_scan(b, s, h, p, n, chunk):
    x = _rand(7, b, s, h, p)
    dt = jax.nn.softplus(_rand(8, b, s, h))
    A = -jnp.exp(_rand(9, h) * 0.5)
    bm, cm = _rand(10, b, s, n), _rand(11, b, s, n)
    out = ops.ssd_scan(x, dt, A, bm, cm, chunk=chunk)
    exp = ref.ssd_scan_sequential_ref(x, dt, A, bm, cm)
    scale = float(jnp.abs(exp).max())
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-4 * max(scale, 1), rtol=1e-4)
    # the chunked jnp reference agrees too (kernel oracle = model impl)
    exp2 = ref.ssd_scan_ref(x, dt, A, bm, cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(exp2), np.asarray(exp),
                               atol=1e-4 * max(scale, 1), rtol=1e-4)


COMBINE_CASES = [(4, 128, 100), (12, 44, 91), (3, 128, 1000), (1, 7, 13)]


@pytest.mark.parametrize("m,seg,c", COMBINE_CASES)
def test_ensemble_combine(m, seg, c):
    p = _rand(12, m, seg, c)
    w = jax.nn.softmax(_rand(13, m))
    out = ops.ensemble_combine(p, w)
    exp = ref.ensemble_combine_ref(p, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-5)


def test_ensemble_combine_is_paper_rule():
    """Uniform weights reproduce Y += P/M exactly."""
    m, seg, c = 5, 16, 10
    p = _rand(14, m, seg, c)
    w = jnp.full((m,), 1.0 / m)
    out = ops.ensemble_combine(p, w)
    acc = np.zeros((seg, c), np.float32)
    for i in range(m):
        acc += np.asarray(p[i]) / m
    np.testing.assert_allclose(np.asarray(out), acc, atol=1e-6)


@pytest.mark.parametrize("m,seg,c", COMBINE_CASES)
def test_ensemble_accumulate(m, seg, c):
    """The accumulate-into-partial kernel variant == partial + weighted sum."""
    p = _rand(15, m, seg, c)
    w = jax.nn.softmax(_rand(16, m))
    part = _rand(17, seg, c)
    out = ops.ensemble_accumulate(part, p, w)
    exp = ref.ensemble_accumulate_ref(part, p, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-5)


def test_ensemble_accumulate_chains():
    """Folding members one at a time (the device combiner's usage) equals a
    single fused combine."""
    m, seg, c = 4, 20, 100                 # non-block-aligned on purpose
    p = _rand(18, m, seg, c)
    w = jax.nn.softmax(_rand(19, m))
    acc = jnp.zeros((seg, c), jnp.float32)
    for i in range(m):
        acc = ops.ensemble_accumulate(acc, p[i][None], w[i][None])
    exp = ref.ensemble_combine_ref(p, w)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(exp), atol=1e-5)


def test_kernels_used_by_models_match():
    """flash_attention kernel path == model jnp path inside self-attention."""
    from repro.configs import get_config
    import repro.models as M
    cfg = get_config("llama3-8b").reduced()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
    l1, _ = M.forward(params, cfg, tokens, use_kernel=False)
    l2, _ = M.forward(params, cfg, tokens, use_kernel=True)
    assert float(jnp.abs(l1 - l2).max()) < 2e-3


GMM_CASES = [
    # (group sizes, k, n): empty groups, rows past the groups, a row count
    # off the row tile
    ((50, 0, 130, 70), 256, 384),
    ((0, 128, 0), 128, 128),
    ((3, 5), 256, 256),
]


def _gmm_loop(lhs, rhs, sizes):
    """Each group's rows times its matrix, one group at a time."""
    out = np.zeros((lhs.shape[0], rhs.shape[2]), np.float64)
    lo = 0
    for g, n in enumerate(sizes):
        out[lo:lo + n] = np.asarray(lhs[lo:lo + n], np.float64) @ \
            np.asarray(rhs[g], np.float64)
        lo += n
    return out, lo


@pytest.mark.parametrize("sizes,k,n", GMM_CASES)
def test_grouped_matmul(sizes, k, n):
    """The CPU path and the Pallas megablox kernel (interpret mode, on the
    bfloat16 operands it is fed on the TPU) against a loop over groups."""
    m = sum(sizes) + 7                           # rows past the groups
    lhs = _rand(11, m, k)
    rhs = _rand(12, len(sizes), k, n)
    gs = jnp.asarray(sizes, jnp.int32)
    want, valid = _gmm_loop(lhs, rhs, sizes)
    got = ops.grouped_matmul(lhs, rhs, gs)
    np.testing.assert_allclose(np.asarray(got[:valid]), want[:valid],
                               rtol=1e-4, atol=1e-3)
    lb, rb = (x.astype(jnp.bfloat16).astype(jnp.float32) for x in (lhs, rhs))
    want_b, _ = _gmm_loop(lb, rb, sizes)
    got_b = ops._gmm(lhs, rhs, gs, interpret=True)
    assert got_b.shape == (m, n) and got_b.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got_b[:valid]), want_b[:valid],
                               rtol=1e-4, atol=1e-3)
