"""Model operations and bytes, from a configuration file's shapes.

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


def _counts(spec: dict) -> tuple[int, int]:
    """(Mamba2 layers, shared-block applications)."""
    order = W.layer_order(spec)
    return (sum(k != "shared" for k, _ in order),
            sum(k == "shared" for k, _ in order))


def mamba_matmul_params(spec: dict) -> int:
    d = spec["d_model"]
    di, H, _, N, G = W.ssm_dims(spec)
    return d * (2 * di + 2 * G * N + H) + di * d


def shared_matmul_params(spec: dict) -> int:
    d, D, F = spec["d_model"], spec["head_dim"], spec["d_ff"]
    H, KV = spec["n_heads"], spec["n_kv_heads"]
    return 2 * d * H * D + 2 * d * KV * D + 3 * d * F


def ssm_flops_per_token(spec: dict) -> int:
    """One Mamba2 layer's convolution and state update for one token:
    decay and input outer product into the (H, N, P) state (3 per entry),
    its read-out by C (2 per entry), and the depthwise convolution."""
    di, H, P, N, G = W.ssm_dims(spec)
    return 5 * H * N * P + 2 * spec["ssm_conv"] * (di + 2 * G * N)


def attn_flops(spec: dict, keys: int) -> int:
    """Scores and weighted values of one query over ``keys`` keys."""
    return 4 * spec["n_heads"] * spec["head_dim"] * keys


def body_flops_per_token(spec: dict) -> int:
    """Every layer's matmuls and state updates, attention scores aside."""
    n_mamba, n_shared = _counts(spec)
    return (n_mamba * (2 * mamba_matmul_params(spec)
                       + ssm_flops_per_token(spec))
            + (n_shared * 2 * shared_matmul_params(spec) if n_shared else 0))


def head_flops(spec: dict) -> int:
    return 2 * spec["vocab_size"] * spec["d_model"]


def prefill_flops(spec: dict, S: int) -> int:
    """A prompt of S tokens, logits for its last position."""
    _, n_shared = _counts(spec)
    attn = n_shared and n_shared * attn_flops(spec, 1) * S * (S + 1) // 2
    return S * body_flops_per_token(spec) + attn + head_flops(spec)


def decode_flops(spec: dict, pos: int) -> int:
    """One decoded token at position ``pos`` (it sees pos + 1 keys)."""
    _, n_shared = _counts(spec)
    attn = n_shared and n_shared * attn_flops(spec, pos + 1)
    return body_flops_per_token(spec) + attn + head_flops(spec)


def weight_bytes(spec: dict, itemsize: int = 2) -> int:
    """Bytes of the weights one decode step reads."""
    n_mamba, n_shared = _counts(spec)
    di, H, _, N, G = W.ssm_dims(spec)
    per_mamba = mamba_matmul_params(spec) + spec["ssm_conv"] * (di + 2 * G * N)
    shared = shared_matmul_params(spec) if n_shared else 0
    return itemsize * (n_mamba * per_mamba + shared
                       + spec["vocab_size"] * spec["d_model"])
