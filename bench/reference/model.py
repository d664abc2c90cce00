"""Building blocks of the plain float32 forward passes of the benchmark's
models; each family (``bench/families/<family>.py``) puts them together.

Straight ``jax.numpy`` with every matmul at ``Precision.HIGHEST``; nothing
here imports the program. :func:`hidden` runs one layer at a time, each
kind of layer one jitted program, so that a full-depth model fits beside
what is left on the chip:

* Mamba2 mixer (arXiv:2405.21060) as the per-token recurrence
  ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T``, ``y_t = C_t h_t + D x_t``,
  after a causal depthwise convolution, gated as ``norm(y * silu(z))``;
  its inner width is its weights';
* causal multi-head attention with grouped key/value heads and rotary
  positions;
* the embedding, and an output head tied to it.

``mode="fp8"`` is the control: the same computation with the inputs of
every matmul rounded to float8 (e4m3, one scale per tensor), the precision
below the bf16 the configurations state.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fp8(t: jax.Array) -> jax.Array:
    """Round to e4m3 with one scale per tensor, back in float32."""
    t = t.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / F8_MAX
    return (t / scale).astype(F8).astype(jnp.float32) * scale


def einsum(mode: str, eq: str, a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + scale.astype(jnp.float32))


def causal_conv(u, w, b):
    """Depthwise causal convolution: out_t = sum_j w_j u_{t-(W-1)+j} + b."""
    Wd = w.shape[0]
    S = u.shape[1]
    up = jnp.pad(u, ((0, 0), (Wd - 1, 0), (0, 0)))
    out = sum(up[:, j:j + S] * w[j].astype(jnp.float32) for j in range(Wd))
    return out + b.astype(jnp.float32)


def mamba_mixer(p, x, spec, mode):
    """x (B,S,d) float32 -> (B,S,d): the Mamba2 mixer, token by token."""
    di, H, P, N, G = W.ssm_dims(spec, p["out_proj"].shape[0])
    Bt, S, _ = x.shape
    proj = einsum(mode, "bsd,de->bse", x, p["in_proj"])
    z = proj[..., :di]
    xBC = proj[..., di:2 * di + 2 * G * N]
    dt_raw = proj[..., 2 * di + 2 * G * N:]
    xBC = jax.nn.silu(causal_conv(xBC, p["conv_w"], p["conv_b"]))
    xs = xBC[..., :di].reshape(Bt, S, H, P)
    Bs = xBC[..., di:di + G * N].reshape(Bt, S, G, N)
    Cs = xBC[..., di + G * N:].reshape(Bt, S, G, N)
    head_group = jnp.arange(H) // (H // G)
    Bh, Ch = Bs[:, :, head_group], Cs[:, :, head_group]      # (B,S,H,N)
    dt = jax.nn.softplus(dt_raw + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    def step(h, inp):
        x_t, B_t, C_t, dt_t = inp                 # (B,H,P) (B,H,N) (B,H,N) (B,H)
        h = (jnp.exp(dt_t * A)[..., None, None] * h
             + (dt_t[..., None] * B_t)[..., :, None] * x_t[..., None, :])
        y = jnp.einsum("bhn,bhnp->bhp", C_t, h, precision=HIGHEST)
        return h, y

    seq = lambda t: jnp.moveaxis(t, 1, 0)
    h0 = jnp.zeros((Bt, H, N, P), jnp.float32)
    _, ys = jax.lax.scan(step, h0, (seq(xs), seq(Bh), seq(Ch), seq(dt)),
                         unroll=4)
    y = jnp.moveaxis(ys, 0, 1)
    y = y + p["D"].astype(jnp.float32)[:, None] * xs
    y = y.reshape(Bt, S, di) * jax.nn.silu(z)
    y = rmsnorm(y, p["norm"]["scale"], spec["norm_eps"])
    return einsum(mode, "bse,ed->bsd", y, p["out_proj"])


def mamba_layer(p, x, spec, mode):
    h = rmsnorm(x, p["ln"]["scale"], spec["norm_eps"])
    return x + mamba_mixer(p["mamba"], h, spec, mode)


def rope(t, theta):
    """Rotate-half rotary embedding over positions 0..S-1; t (B,S,H,D)."""
    S, D = t.shape[1], t.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs    # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    t1, t2 = t[..., :D // 2], t[..., D // 2:]
    return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos], -1)


def attention(a, h, spec, mode):
    """Causal attention of the normed ``h`` (B,S,d) with the weights ``a``
    (``wq``, ``wk``, ``wv``, ``wo``), back in the residual's width."""
    Bt, S, _ = h.shape
    H, KV, D = spec["n_heads"], spec["n_kv_heads"], spec["head_dim"]
    q = einsum(mode, "bsd,de->bse", h, a["wq"]).reshape(Bt, S, H, D)
    k = einsum(mode, "bsd,de->bse", h, a["wk"]).reshape(Bt, S, KV, D)
    v = einsum(mode, "bsd,de->bse", h, a["wv"]).reshape(Bt, S, KV, D)
    q, k = rope(q, spec["rope_theta"]), rope(k, spec["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = einsum(mode, "bqhd,bkhd->bhqk", q, k) / math.sqrt(D)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = einsum(mode, "bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return einsum(mode, "bse,ed->bsd", o.reshape(Bt, S, H * D), a["wo"])


def embed(e, t, spec, mode):
    return e[t].astype(jnp.float32)


def tied_head(e, n, x, spec, mode):
    """Logits of the hidden states ``x`` (n, d) through the final norm and
    the embedding's first ``vocab_size`` rows."""
    V = spec["vocab_size"]
    return einsum(mode, "bd,vd->bv", rmsnorm(x, n, spec["norm_eps"]), e[:V])


@functools.lru_cache(maxsize=None)
def _compiled(fn, spec_items: tuple, mode: str):
    spec = dict(spec_items)
    return jax.jit(lambda *args: fn(*args, spec, mode))


def jitted(fn, spec: dict, mode: str):
    """``fn(*arrays, spec, mode)`` as one jitted program of the arrays,
    built once for each function, configuration and mode."""
    return _compiled(fn, _key(spec), mode)


def _key(spec: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in spec.items()
                        if isinstance(v, (int, float, str, bool))))


def hidden(embedding, tokens, layers, spec: dict, mode: str):
    """Final hidden states (B,S,d) float32 of right-padded ``tokens``:
    the embedding, then each ``(fn, weights)`` of ``layers`` in order as
    ``x = fn(weights, x, spec, mode)``. A family passes ``layers`` as a
    generator, so that only one layer's weights are picked out at a time."""
    with jax.default_matmul_precision("highest"):
        x = jitted(embed, spec, mode)(embedding, tokens)
        for fn, weights in layers:
            x = jitted(fn, spec, mode)(weights, x)
    return x


def tied_logits_at(params, x, rows, cols, spec: dict, mode: str = "f32"):
    """Logits (n, vocab) float32 at positions ``x[rows, cols]``, through
    the output head tied to ``embed``."""
    with jax.default_matmul_precision("highest"):
        sel = x[jnp.asarray(rows), jnp.asarray(cols)]
        return jitted(tied_head, spec, mode)(
            params["embed"], params["final_norm"]["scale"], sel)
