"""Per-kernel shape/dtype sweeps: Pallas (interpreted on the CPU) vs jnp
oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.nbr_sample import nbr_sample, segment_bounds_ref
from repro.kernels.seg_aggr import seg_aggr, seg_aggr_ref
from repro.kernels.ssd_scan import ssd_forward, ssd_ref_sequential

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("shape", [(16, 4, 8), (130, 7, 96), (256, 32, 128),
                                   (100, 1, 300), (1, 64, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("reduce", ["mean", "sum"])
def test_seg_aggr(shape, dtype, reduce):
    n, f, d = shape
    x = jnp.asarray(RNG.normal(size=shape), dtype)
    m = jnp.asarray(RNG.random((n, f)) < 0.7)
    out = seg_aggr(x, m, reduce)
    ref = seg_aggr_ref(x, m, reduce)
    assert out.shape == (n, d) and out.dtype == dtype
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_seg_aggr_all_masked_rows():
    x = jnp.ones((8, 4, 16), jnp.float32)
    m = jnp.zeros((8, 4), bool)
    out = seg_aggr(x, m, "mean")
    np.testing.assert_allclose(np.asarray(out), 0.0)


@pytest.mark.parametrize("reduce", ["mean", "sum"])
def test_seg_aggr_partly_masked_rows(reduce):
    """Fully-masked rows (isolated nodes) emit exactly 0 and a row with
    one valid neighbor emits that neighbor, in both reduce modes."""
    x = jnp.asarray(RNG.normal(size=(10, 6, 24)), jnp.float32)
    m = np.ones((10, 6), bool)
    m[3] = False
    m[7, 1:] = False
    out = np.asarray(seg_aggr(x, jnp.asarray(m), reduce))
    np.testing.assert_array_equal(out[3], 0.0)
    np.testing.assert_allclose(out[7], np.asarray(x[7, 0]), rtol=1e-6)
    np.testing.assert_allclose(
        out, np.asarray(seg_aggr_ref(x, jnp.asarray(m), reduce)),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", [(16, 4, 8), (130, 7, 96), (256, 32, 128),
                                   (100, 1, 300), (1, 64, 16)])
@pytest.mark.parametrize("reduce", ["mean", "sum"])
def test_seg_aggr_grad_sweep(shape, reduce):
    """The kernel's VJP (the oracle's transpose) gives the oracle's
    gradient at every swept shape, as training through
    ``gnn.use_pallas`` needs."""
    n, f, d = shape
    x = jnp.asarray(RNG.normal(size=shape), jnp.float32)
    m = jnp.asarray(RNG.random((n, f)) < 0.7)
    w = jnp.asarray(RNG.normal(size=(n, d)), jnp.float32)
    g = [jax.grad(lambda v: (fn(v, m, reduce) * w).sum())(x)
         for fn in (seg_aggr, seg_aggr_ref)]
    np.testing.assert_allclose(np.asarray(g[0]), np.asarray(g[1]),
                               rtol=1e-6, atol=1e-6)


def test_seg_aggr_grad_matches_oracle():
    x = jnp.asarray(RNG.normal(size=(12, 4, 16)), jnp.float32)
    m = jnp.asarray(RNG.random((12, 4)) < 0.7)
    g = [jax.grad(lambda v: fn(v, m, "mean").sum())(x)
         for fn in (seg_aggr, seg_aggr_ref)]
    np.testing.assert_allclose(np.asarray(g[0]), np.asarray(g[1]),
                               rtol=1e-6, atol=1e-6)


def test_interpret_follows_the_backend(monkeypatch):
    """Kernels interpret on the CPU, compile on a TPU, and refuse an
    explicit interpret=True on a TPU."""
    from repro.kernels import backend
    assert backend.resolve_interpret(None) is True
    assert backend.resolve_interpret(False) is False
    monkeypatch.setattr(backend.jax, "default_backend", lambda: "tpu")
    assert backend.resolve_interpret(None) is False
    with pytest.raises(ValueError, match="TPU"):
        backend.resolve_interpret(True)


# ---------------------------------------------------------------------------
# nbr_sample: segmented random-gather (device-resident neighbor sampling)
# ---------------------------------------------------------------------------
def _random_csr(num_dst, max_deg, num_src, rng, force_zero=()):
    degs = rng.integers(0, max_deg + 1, num_dst)
    for i in force_zero:
        degs[i] = 0
    row_ptr = np.zeros(num_dst + 1, np.int32)
    row_ptr[1:] = np.cumsum(degs)
    e = int(row_ptr[-1])
    col = rng.integers(0, num_src, e).astype(np.int32)
    eid = rng.permutation(e).astype(np.int32)
    return row_ptr, col, eid, degs


@pytest.mark.parametrize("shape", [
    (40, 13, 4),       # small
    (300, 257, 7),     # n not a block multiple, odd fanout
    (64, 128, 32),     # block-sized rows
    (10, 1, 1),        # single dst / fanout 1
])
def test_nbr_sample_kernel_matches_ref(shape):
    """Kernel (interpreted) and jnp oracle consume the same uniform bits,
    so their draws must be bit-identical."""
    num_dst, n, f = shape
    rng = np.random.default_rng(3)
    row_ptr, col, eid, _ = _random_csr(num_dst, 6, 99, rng, force_zero=(0,))
    dst = jnp.asarray(rng.integers(0, num_dst, n), jnp.int32)
    key = jax.random.PRNGKey(11)
    out_ref = nbr_sample(jnp.asarray(row_ptr), jnp.asarray(col),
                         jnp.asarray(eid), dst, key, fanout=f)
    out_ker = nbr_sample(jnp.asarray(row_ptr), jnp.asarray(col),
                         jnp.asarray(eid), dst, key, fanout=f,
                         use_pallas=True)
    for a, b in zip(out_ref, out_ker):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_nbr_sample_draws_stay_in_segment():
    rng = np.random.default_rng(5)
    row_ptr, col, eid, degs = _random_csr(30, 5, 70, rng,
                                          force_zero=(2, 9))
    dst_np = rng.integers(0, 30, 50)
    dst = jnp.asarray(dst_np, jnp.int32)
    key = jax.random.PRNGKey(0)
    nbr, e, m = nbr_sample(jnp.asarray(row_ptr), jnp.asarray(col),
                           jnp.asarray(eid), dst, key, fanout=6)
    nbr, e, m = np.asarray(nbr), np.asarray(e), np.asarray(m)
    starts, dd = segment_bounds_ref(jnp.asarray(row_ptr), dst)
    starts, dd = np.asarray(starts), np.asarray(dd)
    # zero-degree rows fully masked, others fully valid (with replacement)
    np.testing.assert_array_equal(m.all(axis=1), degs[dst_np] > 0)
    np.testing.assert_array_equal(m.any(axis=1), degs[dst_np] > 0)
    for i in range(50):
        if dd[i]:
            seg = set(col[starts[i]:starts[i] + dd[i]].tolist())
            eseg = set(eid[starts[i]:starts[i] + dd[i]].tolist())
            assert set(nbr[i].tolist()) <= seg
            assert set(e[i].tolist()) <= eseg


def test_nbr_sample_key_determines_stream():
    rng = np.random.default_rng(6)
    row_ptr, col, eid, _ = _random_csr(20, 8, 40, rng)
    dst = jnp.asarray(rng.integers(0, 20, 32), jnp.int32)
    args = (jnp.asarray(row_ptr), jnp.asarray(col), jnp.asarray(eid), dst)
    a = nbr_sample(*args, jax.random.PRNGKey(1), fanout=5)
    b = nbr_sample(*args, jax.random.PRNGKey(1), fanout=5)
    c = nbr_sample(*args, jax.random.PRNGKey(2), fanout=5)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert (np.asarray(a[0]) != np.asarray(c[0])).any()


@pytest.mark.parametrize("cfg", [
    (1, 2, 2, 128, 64, 64, 64),
    (2, 4, 2, 256, 32, 128, 128),
    (1, 2, 1, 512, 128, 128, 128),
    (1, 8, 8, 256, 64, 64, 256),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(cfg, causal, dtype):
    B, H, KV, S, D, bq, bk = cfg
    q = jnp.asarray(RNG.normal(size=(B, H, S, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, KV, S, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, KV, S, D)), dtype)
    out = flash_attention(q, k, v, causal=causal, bq=bq, bk=bk)
    kk = jnp.repeat(k, H // KV, 1)
    vv = jnp.repeat(v, H // KV, 1)
    ref = attention_ref(q, kk, vv, causal=causal)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("cfg", [
    (1, 64, 2, 16, 1, 8, 16),
    (2, 128, 4, 32, 2, 16, 32),
    (1, 256, 8, 64, 1, 64, 64),
    (1, 96, 2, 16, 1, 8, 32),
])
def test_ssd_scan(cfg):
    B, S, H, P, G, N, chunk = cfg
    x = jnp.asarray(RNG.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, (B, S, H)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, (H,)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, S, G, N)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(B, S, G, N)), jnp.float32)
    D = jnp.asarray(RNG.normal(size=(H,)), jnp.float32)
    y, st = ssd_forward(x, dt, A, Bm, Cm, D, chunk=chunk)
    yr, sr = ssd_ref_sequential(x, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(st), np.asarray(sr),
                               rtol=2e-3, atol=2e-3)
