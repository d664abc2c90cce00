"""Plain float32 loss and gradients: next-token cross-entropy over the
reference forward pass of the configuration's family
(``bench/families/<family>.py``), averaged over every position, with the
softmax over the configuration's own vocabulary."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import cells


def loss(params, tokens, labels, spec: dict, mode: str = "f32"):
    fam = cells.family(spec)
    x = fam.hidden(params, tokens, spec, mode)
    B, S = tokens.shape
    logits = fam.logits_at(params, x, np.repeat(np.arange(B), S),
                           np.tile(np.arange(S), B), spec, mode)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels.reshape(-1, 1), axis=-1)[:, 0]
    return jnp.mean(lse - picked)


def loss_and_grads(params, tokens, labels, spec: dict, mode: str = "f32"):
    f32 = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    return jax.value_and_grad(loss)(f32, tokens, labels, spec, mode)
