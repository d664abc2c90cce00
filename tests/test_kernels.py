"""Per-kernel validation: shape/dtype sweeps, interpret-mode Pallas vs the
pure-jnp oracle (assignment deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_stats.ops import resolve_impl
from repro.kernels.decode_stats.ref import decode_stats_accumulate_ref
from repro.kernels.decode_stats.stats import decode_stats_accumulate_pallas
from repro.kernels.flash_attention.flash import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.rmsnorm.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd.ref import ssd_ref
from repro.kernels.ssd.ssd import ssd_pallas

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(8, 128), (3, 7, 256), (2, 64, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(shape, dtype):
    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, shape, dtype)
    sc = (jax.random.normal(jax.random.PRNGKey(1), (shape[-1],)) * 0.2)
    out = rmsnorm_pallas(x, sc, interpret=True)
    ref = rmsnorm_ref(x, sc)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------
CASES = [
    dict(causal=True),
    dict(causal=False),
    dict(causal=True, window=32),
    dict(causal=True, chunk=32),
    dict(causal=True, cap=30.0),
    dict(causal=True, window=16, cap=50.0),
]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_variants(case, dtype):
    B, S, H, KV, D = 2, 128, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, D), dtype)
    out = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True,
                          **case)
    ref = attention_ref(q, k, v, **case)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=5 * TOL[dtype], rtol=5 * TOL[dtype])


@pytest.mark.parametrize("shape", [(1, 64, 2, 1, 32), (2, 256, 8, 8, 16),
                                   (1, 96, 6, 3, 64)])
def test_flash_shapes(shape):
    B, S, H, KV, D = shape
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KV, D))
    v = jax.random.normal(ks[2], (B, S, KV, D))
    out = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_flash_uneven_lengths_fall_back_single_block():
    B, S, H, KV, D = 1, 48, 2, 2, 32      # S not divisible by 32
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KV, D))
    v = jax.random.normal(ks[2], (B, S, KV, D))
    out = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(attention_ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# Fused decode partial-stat accumulation (the serve overlap region body)
# ---------------------------------------------------------------------------
DECODE_STATS_CASES = [
    dict(pos=17),                        # plain causal prefix
    dict(pos=100, window=32),            # sliding window
    dict(pos=63, chunk=32),              # chunked-local
    dict(pos=200, ring=True),            # ring cache (slot reuse)
    dict(pos=3, slot_offset=512, total_len=1024),   # fully-masked shard
]


@pytest.mark.parametrize("case", DECODE_STATS_CASES)
@pytest.mark.parametrize("dims", [(1, 8, 4, 64, 128), (2, 6, 2, 32, 96),
                                  (1, 4, 4, 64, 100)])   # ragged last block
def test_decode_stats_kernel(case, dims):
    from repro.models.attention import decode_stats_scores, decode_partial_stats
    B, H, KV, D, L = dims
    case = dict(case)
    pos = case.pop("pos")
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, 1, H, D))
    k = jax.random.normal(ks[1], (B, L, KV, D))
    v = jax.random.normal(ks[2], (B, L, KV, D))
    case.setdefault("total_len", L)
    s, mask = decode_stats_scores(q, k, pos, **case)
    m = jnp.max(s, axis=-1)
    o_ref, l_ref = decode_stats_accumulate_ref(s, m, v)
    o_pl, l_pl = decode_stats_accumulate_pallas(s, m, v, block_k=32,
                                                interpret=True)
    np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(l_pl), np.asarray(l_ref),
                               atol=2e-5, rtol=2e-5)
    if case.get("slot_offset"):          # fully masked: exact zeros
        assert float(jnp.abs(o_pl).max()) == 0.0
        assert float(jnp.abs(l_pl).max()) == 0.0
        return
    # the composed jnp oracle (what the serve region computes without the
    # kernel) agrees too — one scoring/masking path, no drift
    o_j, _, l_j = decode_partial_stats(q, k, v, pos, **case)
    np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_j),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(l_pl), np.asarray(l_j),
                               atol=2e-5, rtol=2e-5)


def test_decode_stats_impl_resolution():
    assert resolve_impl("jnp") == "jnp"
    assert resolve_impl("pallas_interpret") == "pallas_interpret"
    assert resolve_impl("auto") in ("jnp", "pallas")   # pallas iff real TPU
    with pytest.raises(ValueError):
        resolve_impl("cuda")


# ---------------------------------------------------------------------------
# SSD (Mamba2)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dims", [(2, 128, 4, 16, 1, 32), (1, 64, 2, 8, 2, 16),
                                  (2, 96, 6, 32, 3, 8)])
def test_ssd_kernel(dims):
    Bt, S, H, P, G, N = dims
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (Bt, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bt, S, H)))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,)))
    B = jax.random.normal(ks[3], (Bt, S, G, N)) * 0.5
    C = jax.random.normal(ks[4], (Bt, S, G, N)) * 0.5
    Q = 32
    y1, h1 = ssd_pallas(x, dt, A, B, C, Q=Q, interpret=True)
    y2, h2 = ssd_ref(x, dt, A, B, C, Q=Q)
    scale = float(jnp.max(jnp.abs(y2))) + 1e-6
    assert float(jnp.max(jnp.abs(y1 - y2))) / scale < 1e-5
    assert float(jnp.max(jnp.abs(h1 - h2))) < 1e-4


def test_ssd_chunked_grads_finite_at_real_chunk():
    """zamba2's published chunk (Q=128) with the init's decay range (A down
    to -16, dt up to 0.1): the masked upper triangle must not overflow into
    NaN gradients."""
    from repro.models.ssm import ssd_chunked
    Bt, S, H, P, G, N = 1, 128, 2, 8, 1, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (Bt, S, H, P))
    dt = jnp.full((Bt, S, H), 0.1)
    A = -jnp.array([16.0, 1.0])
    B = jax.random.normal(ks[2], (Bt, S, G, N)) * 0.5
    C = jax.random.normal(ks[3], (Bt, S, G, N)) * 0.5

    def loss(x, dt, B, C):
        y, h = ssd_chunked(x, dt, A, B, C, 128)
        return jnp.sum(y.astype(jnp.float32) ** 2) + jnp.sum(h ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2, 3))(x, dt, B, C)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)


def test_ssd_chunk_invariance():
    """Same result whatever the chunk size (the state carry is exact)."""
    Bt, S, H, P, G, N = 1, 128, 2, 8, 1, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (Bt, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bt, S, H)))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,)))
    B = jax.random.normal(ks[3], (Bt, S, G, N)) * 0.5
    C = jax.random.normal(ks[4], (Bt, S, G, N)) * 0.5
    outs = [ssd_pallas(x, dt, A, B, C, Q=q, interpret=True)[0]
            for q in (16, 32, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=1e-4, rtol=1e-4)
