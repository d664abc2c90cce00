"""Random weights made from the seed, in the layout the serving engine reads.

One jitted call makes every leaf on the device, in the type it is served
in (bf16), from ``--seed``. The same tree feeds the program under test and
the plain reference, so the two run on identical numbers; nothing here
imports the program.

Layout (what ``repro.models.transformer`` consumes): ``embed`` (rows, d)
tied to the output head; ``final_norm``; for the hybrid family one
``shared_attn`` block; ``blocks`` holding one stacked slot per position in
the layer period (the shared block's slot is empty, its weights live once
at the top); ``rest`` the Mamba2 layers after the last whole period.
Norm weights are stored as ``scale`` with the norm multiplying by
``1 + scale``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def ssm_dims(spec: dict) -> tuple[int, int, int, int, int]:
    """(d_inner, heads, head size, state size, groups) of a Mamba2 layer."""
    d_inner = spec["ssm_expand"] * spec["d_model"]
    P = spec["ssm_headdim"]
    return d_inner, d_inner // P, P, spec["ssm_state"], spec["ssm_ngroups"]


def period(spec: dict) -> tuple[int, int, int]:
    """(layers per period, whole periods, Mamba2 layers left over).

    A hybrid model applies the shared block after every
    ``shared_attn_every`` Mamba2 layers, so its period is that many Mamba2
    layers plus the block; an SSM model's period is one layer."""
    n = spec["n_layers"]
    if spec["family"] == "hybrid":
        k = spec["shared_attn_every"]
        return k + 1, n // k, n % k
    if spec["family"] == "ssm":
        return 1, n, 0
    raise ValueError(f"no weight layout for family {spec['family']!r}")


def layer_order(spec: dict) -> list[tuple[str, object]]:
    """The model's layers in order: ("mamba", (slot, rep)) for a stacked
    Mamba2 layer, ("rest", i) for a leftover one, ("shared", None) for an
    application of the shared block."""
    pi, reps, rem = period(spec)
    out: list[tuple[str, object]] = []
    for r in range(reps):
        for j in range(pi):
            if spec["family"] == "hybrid" and j == pi - 1:
                out.append(("shared", None))
            else:
                out.append(("mamba", (j, r)))
    out += [("rest", i) for i in range(rem)]
    return out


def _mamba_shapes(spec: dict) -> dict:
    d = spec["d_model"]
    di, H, _, N, G = ssm_dims(spec)
    conv_ch = di + 2 * G * N
    return {"ln": {"scale": (d,)},
            "mamba": {"in_proj": (d, 2 * di + 2 * G * N + H),
                      "conv_w": (spec["ssm_conv"], conv_ch),
                      "conv_b": (conv_ch,), "dt_bias": (H,), "A_log": (H,),
                      "D": (H,), "norm": {"scale": (di,)},
                      "out_proj": (di, d)}}


def _shared_shapes(spec: dict) -> dict:
    d, D = spec["d_model"], spec["head_dim"]
    H, KV, F = spec["n_heads"], spec["n_kv_heads"], spec["d_ff"]
    return {"ln1": {"scale": (d,)},
            "attn": {"wq": (d, H * D), "wk": (d, KV * D), "wv": (d, KV * D),
                     "wo": (H * D, d)},
            "ln2": {"scale": (d,)},
            "mlp": {"gate": (d, F), "up": (d, F), "down": (F, d)}}


def shapes(spec: dict, embed_rows: int) -> dict:
    """The tree of leaf shapes."""
    pi, reps, rem = period(spec)
    stack = lambda tree: jax.tree.map(lambda s: (reps,) + s, tree,
                                      is_leaf=lambda s: isinstance(s, tuple))
    blocks = {}
    for j in range(pi):
        shared_slot = spec["family"] == "hybrid" and j == pi - 1
        blocks[f"slot{j}"] = {} if shared_slot else stack(_mamba_shapes(spec))
    tree = {"embed": (embed_rows, spec["d_model"]),
            "final_norm": {"scale": (spec["d_model"],)},
            "blocks": blocks,
            "rest": [_mamba_shapes(spec) for _ in range(rem)]}
    if spec["family"] == "hybrid":
        tree["shared_attn"] = _shared_shapes(spec)
    return tree


def _leaf(key, name: str, shape: tuple, spec: dict, dtype):
    """One leaf's values; ``name`` is the leaf's key in its dict."""
    normal = lambda scale: (jax.random.normal(key, shape, jnp.float32)
                            * scale).astype(dtype)
    if name == "embed":
        rows = jnp.arange(shape[0])[:, None] < spec["vocab_size"]
        return jnp.where(rows, normal(0.02), 0).astype(dtype)
    if name == "scale":
        return normal(0.1)
    if name == "conv_w":
        return normal(1.0 / math.sqrt(spec["ssm_conv"]))
    if name == "conv_b":
        return normal(0.02)
    if name == "dt_bias":
        # dt log-uniform in [1e-3, 0.1], stored as softplus^-1(dt)
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if name == "A_log":
        u = jax.random.uniform(key, shape, jnp.float32)
        return jnp.log(1.0 + 15.0 * u).astype(dtype)
    if name == "D":
        return (1.0 + 0.1 * jax.random.normal(key, shape)).astype(dtype)
    # a projection: fan-in scaling over its input (second-to-last) dim
    return normal(1.0 / math.sqrt(shape[-2]))


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number, 64-bit seeds included."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    key = jax.random.PRNGKey(0)
    for w in words:
        key = jax.random.fold_in(key, jnp.uint32(int(w)))
    return key


def make(spec: dict, seed: int, embed_rows: int, *, sharding=None) -> dict:
    """Every weight, made on the device in one jitted call."""
    dtype = DTYPES[spec["weights_dtype"]]
    tree = shapes(spec, embed_rows)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda s: isinstance(s, tuple))

    def build(key):
        keys = jax.random.split(key, len(paths))
        leaves = [_leaf(k, str(path[-1].key), shp, spec, dtype)
                  for k, (path, shp) in zip(keys, paths)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    fn = jax.jit(build, out_shardings=sharding)
    return fn(seed_key(seed))


def mamba_layer(params: dict, where) -> dict:
    """One Mamba2 layer's weights, picked out of the stacked tree."""
    kind, at = where
    if kind == "rest":
        return params["rest"][at]
    j, r = at
    return jax.tree.map(lambda t: t[r], params["blocks"][f"slot{j}"])
