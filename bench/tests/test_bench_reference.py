"""The plain references against the program, at a size the CPU runs.

Both sides take the same weights from ``bench.weights`` and run in
float32, so they differ only in how they compute: the program's chunked
SSD scan against the reference's token-by-token recurrence, attention
over the cache against full causal attention, the cached decode step
against a fresh forward. The program's chunked SSD keeps its inner
products in bf16 whatever the configuration's dtype, and its GeGLU uses
the tanh form of GELU where the reference takes the exact one.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells, weights as W
from bench.drivers.serve import program_config
from bench.reference import train as ref_train
from bench.tests.smoke import smoke_cell

# every cell of the benchmark, so that a configuration added by files gets
# these tests with no edit
CELLS = tuple(w["name"] for w in json.loads(
    (Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text()
)["workloads"])
# max |program - reference| / max |reference|. The SSD's bf16 products
# (2^-8 relative) measured 2.7e-3 over 4-6 layers; an equation wrong in
# one place (conv offset, gate, decay sign) reads O(1).
TOL = 1e-2


def _setup(name: str, **sizes):
    spec = dict(smoke_cell(name).config, weights_dtype="float32",
                dtype="float32", **sizes)
    cfg = program_config(spec)
    params = W.make(spec, seed=2**32 + 7, embed_rows=cfg.padded_vocab)
    return spec, cfg, params


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


@pytest.mark.parametrize("name", CELLS)
def test_weight_tree_is_the_programs(name):
    from repro.models import transformer
    spec, cfg, params = _setup(name)
    want = jax.eval_shape(lambda k: transformer.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    assert (jax.tree.structure(params) == jax.tree.structure(want))
    assert all(a.shape == b.shape for a, b in
               zip(jax.tree.leaves(params), jax.tree.leaves(want)))


@pytest.mark.parametrize("name", CELLS)
def test_published_sizes_give_the_programs_parameter_count(name):
    """Full size, by arithmetic only: nothing is traced or allocated."""
    spec = cells.load_cell(name).config
    cfg = program_config(spec)
    tree = cells.family(spec).shapes(spec, cfg.padded_vocab)
    n = sum(int(np.prod(s)) for s in jax.tree.leaves(
        tree, is_leaf=lambda s: isinstance(s, tuple)))
    assert n == cfg.param_count()


@pytest.mark.parametrize("name,wide_heads", [
    *(pytest.param(c, False, id=c) for c in CELLS),
    # Zamba2's shared block attends at twice d_model (head size
    # 2 d_model / heads), wider than the residual stream it reads and writes
    pytest.param("zamba2-1.2b.chat", True, id="zamba2-1.2b.chat-wide-heads")])
def test_prefill_then_decode_matches_reference(name, wide_heads):
    """The program prefills a prompt, decodes through its caches, and its
    logits at every step agree with the reference's forward pass."""
    from repro.models import transformer
    sizes = {}
    if wide_heads:
        spec = smoke_cell(name).config
        sizes["head_dim"] = 2 * spec["d_model"] // spec["n_heads"]
    spec, cfg, params = _setup(name, **sizes)
    if wide_heads:
        assert params["shared_attn"]["attn"]["wq"].shape == (
            spec["d_model"], 2 * spec["d_model"])
    rng = np.random.default_rng(3)
    P, steps = 40, 6
    toks = rng.integers(0, spec["vocab_size"], P + steps, dtype=np.int32)
    logits, _, cache = transformer.forward(params, cfg, jnp.asarray(toks[None, :P]),
                                           mode="prefill", cache_len=64)
    got = [logits[0, -1, :spec["vocab_size"]]]
    for t in range(steps - 1):
        logits, _, cache = transformer.forward(
            params, cfg, jnp.asarray(toks[None, P + t:P + t + 1]),
            cache=cache)
        got.append(logits[0, -1, :spec["vocab_size"]])
    fam = cells.family(spec)
    x = fam.hidden(params, jnp.asarray(toks[None]), spec)
    want = fam.logits_at(params, x, [0] * steps, list(range(P - 1,
                                                           P - 1 + steps)),
                         spec)
    assert _rel(jnp.stack(got), want) < TOL


@pytest.mark.parametrize("name", CELLS)
def test_train_loss_and_gradients_match_reference(name):
    from repro.train.step import make_loss_fn
    spec, cfg, params = _setup(name)
    rng = np.random.default_rng(4)
    toks = rng.integers(0, spec["vocab_size"], (2, 64), dtype=np.int32)
    batch = {"tokens": jnp.asarray(toks[:, :-1]),
             "labels": jnp.asarray(toks[:, 1:])}
    loss_fn = make_loss_fn(cfg, remat=False)
    (loss, _), grads = jax.value_and_grad(
        lambda p: loss_fn(p, batch, lambda t, _k: t), has_aux=True)(params)
    want_loss, want_grads = ref_train.loss_and_grads(
        params, batch["tokens"], batch["labels"], spec)
    assert abs(float(loss) - float(want_loss)) < TOL * float(want_loss)
    # each leaf's error against its own norm or the median leaf's, the
    # larger: dt_bias and A_log hold gradients ~100x smaller than the rest
    norms = [float(jnp.linalg.norm(w)) for w in jax.tree.leaves(want_grads)]
    floor = float(np.median(norms))
    for g, w, n in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads),
                       norms):
        assert float(jnp.linalg.norm(g - w)) <= TOL * max(n, floor)


def test_fp8_control_departs_from_reference():
    """The control really computes in a lower precision."""
    spec, cfg, params = _setup("zamba2-1.2b.chat")
    toks = jnp.asarray(np.random.default_rng(5).integers(
        0, spec["vocab_size"], (1, 32), dtype=np.int32))
    fam = cells.family(spec)
    a = fam.hidden(params, toks, spec)
    b = fam.hidden(params, toks, spec, "fp8")
    assert _rel(b, a) > 1e-2
