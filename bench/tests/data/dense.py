"""A family module for the program's ``dense`` family, kept as a fixture:
the plug-in test copies it into a copy of the benchmark as
``families/dense.py`` to show that a new family enters by adding files.

Each layer is pre-norm causal attention with grouped key/value heads and
rotary positions, then a pre-norm SwiGLU MLP, both residual. The output
head is a matrix of its own, ``head`` (d, rows), whose columns past the
vocabulary are zero, as a loaded checkpoint leaves them.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import flops as F, weights as W
from bench.reference import model as ref


def _layer_shapes(spec: dict) -> dict:
    d, D = spec["d_model"], spec["head_dim"]
    H, KV, F_ = spec["n_heads"], spec["n_kv_heads"], spec["d_ff"]
    return {"ln1": {"scale": (d,)},
            "attn": {"wq": (d, H * D), "wk": (d, KV * D), "wv": (d, KV * D),
                     "wo": (H * D, d)},
            "ln2": {"scale": (d,)},
            "mlp": {"gate": (d, F_), "up": (d, F_), "down": (F_, d)}}


def shapes(spec: dict, embed_rows: int) -> dict:
    d = spec["d_model"]
    return {"embed": (embed_rows, d), "final_norm": {"scale": (d,)},
            "head": (d, embed_rows),
            "blocks": {"slot0": W.stacked(_layer_shapes(spec),
                                          spec["n_layers"])},
            "rest": []}


def _head(key, name, shape, spec, dtype):
    cols = jnp.arange(shape[1])[None, :] < spec["vocab_size"]
    w = jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[0])
    return jnp.where(cols, w, 0).astype(dtype)


LEAVES = {"head": _head}


def block(p, x, spec, mode):
    eps = spec["norm_eps"]
    x = x + ref.attention(p["attn"], ref.rmsnorm(x, p["ln1"]["scale"], eps),
                          spec, mode)
    m = p["mlp"]
    h = ref.rmsnorm(x, p["ln2"]["scale"], eps)
    g = jax.nn.silu(ref.einsum(mode, "bsd,df->bsf", h, m["gate"]))
    u = ref.einsum(mode, "bsd,df->bsf", h, m["up"])
    return x + ref.einsum(mode, "bsf,fd->bsd", g * u, m["down"])


def hidden(params, tokens, spec: dict, mode: str = "f32"):
    layers = ((block, W.layer_weights(params, ("attn", (0, r))))
              for r in range(spec["n_layers"]))
    return ref.hidden(params["embed"], tokens, layers, spec, mode)


def _head_logits(w, n, x, spec, mode):
    V = spec["vocab_size"]
    return ref.einsum(mode, "bd,dv->bv",
                      ref.rmsnorm(x, n, spec["norm_eps"]), w[:, :V])


def logits_at(params, x, rows, cols, spec: dict, mode: str = "f32"):
    with jax.default_matmul_precision("highest"):
        sel = x[jnp.asarray(rows), jnp.asarray(cols)]
        return ref.jitted(_head_logits, spec, mode)(
            params["head"], params["final_norm"]["scale"], sel)


def _layer_params(spec: dict) -> int:
    return F.attn_matmul_params(spec) + F.gated_mlp_params(spec)


def prefill_flops(spec: dict, S: int) -> int:
    return (S * spec["n_layers"] * 2 * _layer_params(spec)
            + spec["n_layers"] * F.causal_attn_flops(spec, S)
            + F.head_flops(spec))


def decode_flops(spec: dict, pos: int) -> int:
    return (spec["n_layers"] * (2 * _layer_params(spec)
                                + F.attn_flops(spec, pos + 1))
            + F.head_flops(spec))


def weight_bytes(spec: dict, itemsize: int = 2) -> int:
    """The layers and the head's ``vocab_size`` columns; of the embedding
    a step reads only its tokens' rows."""
    return itemsize * (spec["n_layers"] * _layer_params(spec)
                       + spec["vocab_size"] * spec["d_model"])


def smoke(config: dict) -> dict:
    return dict(config, n_layers=3)
