"""Per-row token writes into the stacked decode cache.

``transformer.forward`` carries the stacked ``cache["blocks"]`` through its
layer scan, and an attention slot writes only this step's token, at
(layer, row, pos[row]). A batch whose rows sit at different positions,
ring wraps included, must decode as each row does alone. (That the
compiled step updates the cache in place is checked on the chip's
compiler, in ``test_tpu_compile.py``.)
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import transformer

TOL = 0.05                      # as tests/test_serve_consistency.py


def _stack_rows(caches):
    """B=1 caches (scalar pos) -> one batch cache with a (B,) pos."""
    def cat(path, *leaves):
        top = path[0].key
        if top == "pos":
            return jnp.stack(leaves)
        return jnp.concatenate(leaves, axis=1 if top == "blocks" else 0)
    return jax.tree_util.tree_map_with_path(cat, *caches)


def _row(cache, b):
    def take(path, leaf):
        top = path[0].key
        if top == "pos":
            return leaf[b]
        return leaf[:, b:b + 1] if top == "blocks" else leaf[b:b + 1]
    return jax.tree_util.tree_map_with_path(take, cache)


# zamba2: rows far apart in a full cache; gemma2: window 64, so the row at
# 62 wraps its ring during the steps and the row at 70 has wrapped already
@pytest.mark.parametrize("arch,lengths,cache_len", [
    ("zamba2-1.2b", (3, 17, 40, 9), 48),
    ("gemma2-9b", (5, 62, 70), 80),
])
def test_batched_token_writes_match_rows_alone(arch, lengths, cache_len):
    cfg = configs.get_smoke(arch)
    if arch == "gemma2-9b":
        cfg = dataclasses.replace(cfg, n_layers=4)
    _, reps, _ = transformer.find_period(cfg.layer_plan())
    assert reps >= 2
    steps = 3
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (1, n), np.int32)
               for n in lengths]
    nxt = rng.integers(0, cfg.vocab_size, (len(lengths), steps), np.int32)

    prefill = jax.jit(lambda p, t: transformer.forward(
        p, cfg, t, mode="prefill", cache_len=cache_len)[2])
    decode = jax.jit(lambda p, c, t: transformer.forward(p, cfg, t,
                                                         cache=c)[::2],
                     donate_argnums=(1,))
    alone = [prefill(params, jnp.asarray(t)) for t in prompts]
    batch = _stack_rows(alone)
    f32 = lambda t: np.asarray(t, np.float32)
    for s in range(steps):
        lg, batch = decode(params, batch, jnp.asarray(nxt[:, s:s + 1]))
        for b in range(len(lengths)):
            lg_b, alone[b] = decode(params, alone[b],
                                    jnp.asarray(nxt[b:b + 1, s:s + 1]))
            err = np.max(np.abs(f32(lg[b:b + 1]) - f32(lg_b)))
            assert err < TOL, f"step {s} row {b}: logits {err}"
    np.testing.assert_array_equal(batch["pos"],
                                  np.asarray(lengths) + steps)
    for b in range(len(lengths)):
        row = _row(batch, b)
        for (path, got), want in zip(
                jax.tree_util.tree_flatten_with_path(row)[0],
                jax.tree.leaves(alone[b])):
            err = np.max(np.abs(f32(got) - f32(want)))
            assert err < TOL, f"row {b} {jax.tree_util.keystr(path)}: {err}"
