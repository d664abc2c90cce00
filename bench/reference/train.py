"""Plain float32 loss and gradients: next-token cross-entropy over the
reference forward pass (:mod:`bench.reference.model`), averaged over every
position, with the softmax over the configuration's own vocabulary."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import model as ref


def loss(params, tokens, labels, spec: dict, mode: str = "f32"):
    x = ref.hidden(params, tokens, spec, mode)
    h = ref.rmsnorm(x, params["final_norm"]["scale"], spec["norm_eps"])
    emb = params["embed"][:spec["vocab_size"]]
    with jax.default_matmul_precision("highest"):
        logits = ref.einsum(mode, "bsd,vd->bsv", h, emb)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def loss_and_grads(params, tokens, labels, spec: dict, mode: str = "f32"):
    f32 = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    return jax.value_and_grad(loss)(f32, tokens, labels, spec, mode)
