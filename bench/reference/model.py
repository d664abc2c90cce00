"""Plain float32 forward pass of the benchmark's models.

Straight ``jax.numpy`` with every matmul at ``Precision.HIGHEST``; nothing
here imports the program. It reads the weight tree that
:mod:`bench.weights` lays out and runs one layer at a time, so that a
full-depth model fits beside what is left on the chip:

* Mamba2 mixer (arXiv:2405.21060) as the per-token recurrence
  ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T``, ``y_t = C_t h_t + D x_t``,
  after a causal depthwise convolution, gated as ``norm(y * silu(z))``;
* the hybrid family's shared block (Zamba2, arXiv:2411.15242, as the
  configuration file's ``departures`` describe it): pre-norm causal
  multi-head attention with rotary positions, then a pre-norm GeGLU MLP.

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
    di, H, P, N, G = W.ssm_dims(spec)
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


def shared_block(p, x, spec, mode):
    """Pre-norm causal attention + pre-norm GeGLU MLP, both residual."""
    Bt, S, _ = x.shape
    H, KV, D = spec["n_heads"], spec["n_kv_heads"], spec["head_dim"]
    a = p["attn"]
    h = rmsnorm(x, p["ln1"]["scale"], spec["norm_eps"])
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
    x = x + einsum(mode, "bse,ed->bsd", o.reshape(Bt, S, H * D), a["wo"])
    m = p["mlp"]
    h = rmsnorm(x, p["ln2"]["scale"], spec["norm_eps"])
    g = jax.nn.gelu(einsum(mode, "bsd,df->bsf", h, m["gate"]),
                    approximate=False)
    u = einsum(mode, "bsd,df->bsf", h, m["up"])
    return x + einsum(mode, "bsf,fd->bsd", g * u, m["down"])


@functools.lru_cache(maxsize=None)
def _compiled(kind: str, spec_items: tuple, mode: str):
    spec = dict(spec_items)
    if kind == "mamba":
        return jax.jit(lambda p, x: mamba_layer(p, x, spec, mode))
    if kind == "shared":
        return jax.jit(lambda p, x: shared_block(p, x, spec, mode))
    if kind == "embed":
        return jax.jit(lambda e, t: e[t].astype(jnp.float32))
    if kind == "head":
        V = spec["vocab_size"]
        return jax.jit(lambda e, n, x: einsum(
            mode, "bd,vd->bv", rmsnorm(x, n, spec["norm_eps"]), e[:V]))
    raise ValueError(kind)


def _key(spec: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in spec.items()
                        if isinstance(v, (int, float, str, bool))))


def hidden(params, tokens, spec: dict, mode: str = "f32"):
    """Final hidden states (B,S,d) float32 of right-padded ``tokens``."""
    key = _key(spec)
    with jax.default_matmul_precision("highest"):
        x = _compiled("embed", key, mode)(params["embed"], tokens)
        for kind, where in W.layer_order(spec):
            if kind == "shared":
                x = _compiled("shared", key, mode)(params["shared_attn"], x)
            else:
                x = _compiled("mamba", key, mode)(
                    W.mamba_layer(params, (kind, where)), x)
    return x


def logits_at(params, x, rows, cols, spec: dict, mode: str = "f32"):
    """Logits (n, vocab) float32 at positions ``x[rows, cols]``."""
    with jax.default_matmul_precision("highest"):
        sel = x[jnp.asarray(rows), jnp.asarray(cols)]
        return _compiled("head", _key(spec), mode)(
            params["embed"], params["final_norm"]["scale"], sel)
