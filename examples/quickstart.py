"""Quickstart: the whole stack in one minute on one CPU device.

    PYTHONPATH=src python examples/quickstart.py

1. Build a tiny llama-family model from the config registry.
2. Train it on the synthetic affine-token stream until loss visibly drops.
3. Serve it: prefill + greedy decode with a KV cache.
4. Compare allgather algorithms with the paper's cost model.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core.autotune import model_costs
from repro.data import SyntheticLM
from repro.launch.mesh import make_mesh
from repro.models import transformer
from repro.optim import AdamW, TrainState
from repro.serve import Engine, Request, ServeSpec
from repro.train.step import make_loss_fn


def main():
    # --- 1. model -----------------------------------------------------------
    cfg = dataclasses.replace(configs.get_smoke("llama3.2-3b"), n_layers=2,
                              vocab_size=97, vocab_pad_multiple=1)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"model: {cfg.name}, {n/1e6:.2f}M params")

    # --- 2. train -----------------------------------------------------------
    data = SyntheticLM(vocab_size=97, seq_len=64, global_batch=8, noise=0.02)
    loss_fn = make_loss_fn(cfg)
    opt = AdamW(lr=5e-3)
    state = TrainState.create(params)

    @jax.jit
    def step(state, tokens, labels):
        (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, {"tokens": tokens, "labels": labels},
            lambda x, _k: x)
        state, _ = opt.apply(state, g)
        return state, l

    for i in range(50):
        b = data.batch(i)
        state, l = step(state, jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]))
        if i % 10 == 0 or i == 49:
            print(f"  step {i:3d} loss {float(l):.3f}")

    # --- 3. serve -----------------------------------------------------------
    mesh = make_mesh((1,), ("data",))
    jax.set_mesh(mesh)
    eng = Engine(cfg, mesh, state.params, ServeSpec(batch=4, cache_len=48))
    prompts = data.batch(999)["tokens"][:4, :16]
    for i in range(4):
        eng.submit(Request(tokens=np.asarray(prompts[i]), max_new=8))
    results = eng.drain()
    print("generated continuations:", results[0].tokens)

    # --- 4. the paper's trade-off, in numbers --------------------------------
    print("\nmodeled allgather cost on 4096 ranks, 16/region, 8B msgs (Lassen):")
    for name, cost in sorted(model_costs(4096, 16, 8.0, "lassen").items(),
                             key=lambda kv: kv[1]):
        print(f"  {name:16s} {cost*1e6:9.1f} us")


if __name__ == "__main__":
    main()
