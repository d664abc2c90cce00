"""End-to-end system behaviour: the public API wired together on one device.

(The multi-device variants live in test_distributed.py / test_train.py;
this file guards the single-host path users hit first.)
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.models import transformer
from repro.launch.mesh import make_mesh
from repro.serve import Engine, ServeSpec


def test_end_to_end_tiny_train_then_serve(tmp_path):
    """Train a tiny model until loss drops, then serve it and check the
    generated continuations follow the learned affine token structure."""
    from repro.data import SyntheticLM
    from repro.optim import AdamW, TrainState
    from repro.train.step import make_loss_fn

    cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2,
                              vocab_size=97, vocab_pad_multiple=1)
    rng = jax.random.PRNGKey(0)
    params = transformer.init_params(rng, cfg)
    data = SyntheticLM(vocab_size=97, seq_len=32, global_batch=8, seed=0,
                       noise=0.0)
    loss_fn = make_loss_fn(cfg)
    opt = AdamW(lr=5e-3)
    state = TrainState.create(params)
    shard = lambda x, _k: x

    @jax.jit
    def step(state, tokens, labels):
        (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, {"tokens": tokens, "labels": labels}, shard)
        state, _ = opt.apply(state, g)
        return state, l

    losses = []
    for i in range(60):
        b = data.batch(i)
        state, l = step(state, jnp.asarray(b["tokens"]),
                        jnp.asarray(b["labels"]))
        losses.append(float(l))
    assert losses[-1] < losses[0] - 1.0, (losses[0], losses[-1])

    # serve greedily; verify continuation follows tokens[t+1] = a*t + c
    mesh = make_mesh((1,), ("data",))
    jax.set_mesh(mesh)
    eng = Engine(cfg, mesh, state.params, ServeSpec(batch=4, cache_len=48))
    b = data.batch(1000)
    prompts = b["tokens"][:4, :16]
    toks = eng.generate(prompts, max_new=8)
    V, a = 97, 31337 % 97
    c = (b["labels"][0, 0] - a * b["tokens"][0, 0]) % V
    cur = prompts[:, -1].astype(np.int64)
    hits = total = 0
    for j in range(8):
        expect = (a * cur + c) % V
        hits += int((toks[:, j] == expect).sum())
        total += 4
        cur = toks[:, j].astype(np.int64)
    assert hits / total > 0.5, f"served continuations wrong ({hits}/{total})"
