"""Model operations and bytes, from a configuration file's shapes: the
peak table, and the counts of each kind of layer, which each family
(``bench/families/<family>.py``) sums over its layers.

These are the operations the model requires, not what an implementation
happens to execute: a matmul of an (m, k) by a (k, n) counts 2mkn, causal
attention counts only the keys a query may see, and the Mamba2 state
update counts the recurrence, whatever chunked form computes it.
Element-wise work (norms, gates, activations) is left out. Serving
computes logits for the last prompt position only, so a prefill counts the
output head once.
"""
from __future__ import annotations

import json
from pathlib import Path

from bench import weights as W

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str) -> dict:
    """The peak table's row for a device kind; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def mamba_matmul_params(spec: dict, d_inner: int) -> int:
    d = spec["d_model"]
    di, H, _, N, G = W.ssm_dims(spec, d_inner)
    return d * (2 * di + 2 * G * N + H) + di * d


def mamba_weight_params(spec: dict, d_inner: int) -> int:
    """The weights one Mamba2 layer reads: its matmuls and convolution."""
    di, H, _, N, G = W.ssm_dims(spec, d_inner)
    return mamba_matmul_params(spec, d_inner) + spec["ssm_conv"] * (
        di + 2 * G * N)


def ssm_flops_per_token(spec: dict, d_inner: int) -> int:
    """One Mamba2 layer's convolution and state update for one token:
    decay and input outer product into the (H, N, P) state (3 per entry),
    its read-out by C (2 per entry), and the depthwise convolution."""
    di, H, P, N, G = W.ssm_dims(spec, d_inner)
    return 5 * H * N * P + 2 * spec["ssm_conv"] * (di + 2 * G * N)


def attn_matmul_params(spec: dict) -> int:
    """Query, key, value and output projections of an attention layer."""
    d, D = spec["d_model"], spec["head_dim"]
    H, KV = spec["n_heads"], spec["n_kv_heads"]
    return 2 * d * H * D + 2 * d * KV * D


def gated_mlp_params(spec: dict) -> int:
    """Gate, up and down projections of a gated MLP of width ``d_ff``."""
    return 3 * spec["d_model"] * spec["d_ff"]


def attn_flops(spec: dict, keys: int) -> int:
    """Scores and weighted values of one query over ``keys`` keys."""
    return 4 * spec["n_heads"] * spec["head_dim"] * keys


def causal_attn_flops(spec: dict, S: int) -> int:
    """A prompt of S tokens in one attention layer: query t sees t keys."""
    return attn_flops(spec, 1) * S * (S + 1) // 2


def head_flops(spec: dict) -> int:
    return 2 * spec["vocab_size"] * spec["d_model"]
