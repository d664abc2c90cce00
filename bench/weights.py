"""Random weights made from the seed, in the layout the serving engine reads.

One jitted call makes every leaf on the device, in the type it is served
in (bf16), from ``--seed``. The same tree feeds the program under test and
the plain reference, so the two run on identical numbers; nothing here
imports the program.

The tree of leaf shapes is each family's (``shapes`` of
``families/<family>.py``, found by ``cells.family``): ``embed`` (rows, d),
``final_norm``, ``blocks`` holding one stacked slot per position in the
layer period, ``rest`` the layers after the last whole period, and what
else the family holds. Here are the rules that make a leaf's values from
its name, which a family may extend with a ``LEAVES`` dict of its own
(name -> ``fn(key, name, shape, spec, dtype)``), and the Mamba2 layer's
shapes, which several families use. Norm weights are stored as ``scale``
with the norm multiplying by ``1 + scale``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def ssm_dims(spec: dict, d_inner: int) -> tuple[int, int, int, int, int]:
    """(d_inner, heads, head size, state size, groups) of a Mamba2 layer
    whose inner width the family gives."""
    P = spec["ssm_headdim"]
    return d_inner, d_inner // P, P, spec["ssm_state"], spec["ssm_ngroups"]


def mamba_shapes(spec: dict, d_inner: int) -> dict:
    """One pre-norm Mamba2 layer: ``ln`` and the ``mamba`` mixer."""
    d = spec["d_model"]
    di, H, _, N, G = ssm_dims(spec, d_inner)
    conv_ch = di + 2 * G * N
    return {"ln": {"scale": (d,)},
            "mamba": {"in_proj": (d, 2 * di + 2 * G * N + H),
                      "conv_w": (spec["ssm_conv"], conv_ch),
                      "conv_b": (conv_ch,), "dt_bias": (H,), "A_log": (H,),
                      "D": (H,), "norm": {"scale": (di,)},
                      "out_proj": (di, d)}}


def stacked(tree: dict, reps: int) -> dict:
    """``tree``'s shapes with a leading axis of ``reps`` layers."""
    return jax.tree.map(lambda s: (reps,) + s, tree,
                        is_leaf=lambda s: isinstance(s, tuple))


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
    from bench import cells
    fam = cells.family(spec)
    own = getattr(fam, "LEAVES", {})
    dtype = DTYPES[spec["weights_dtype"]]
    tree = fam.shapes(spec, embed_rows)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda s: isinstance(s, tuple))

    def build(key):
        keys = jax.random.split(key, len(paths))
        names = [str(path[-1].key) for path, _ in paths]
        leaves = [own.get(name, _leaf)(k, name, shp, spec, dtype)
                  for k, name, (_, shp) in zip(keys, names, paths)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    fn = jax.jit(build, out_shardings=sharding)
    return fn(seed_key(seed))


def layer_weights(params: dict, where) -> dict:
    """One layer's weights: ("rest", i) for a layer after the last whole
    period, else (kind, (slot, rep)) picked out of the stacked tree."""
    kind, at = where
    if kind == "rest":
        return params["rest"][at]
    j, r = at
    return jax.tree.map(lambda t: t[r], params["blocks"][f"slot{j}"])
