"""The hybrid family (Zamba2, arXiv:2411.15242, as the configuration
file's ``departures`` describe it): a trunk of Mamba2 layers, and one
attention + MLP block whose weights are shared, applied after every
``shared_attn_every`` Mamba2 layers. The output head is tied to the
embedding.

Layout (what ``repro.models.transformer`` consumes): ``embed`` (rows, d);
``final_norm``; one ``shared_attn`` block; ``blocks`` holding one stacked
slot per position in the period of ``shared_attn_every`` Mamba2 layers and
the block (the block's slot is empty, its weights live once at the top);
``rest`` the Mamba2 layers after the last whole period.
"""
from __future__ import annotations

import jax

from bench import flops as F, weights as W
from bench.reference import model as ref


def d_inner(spec: dict) -> int:
    return spec["ssm_expand"] * spec["d_model"]


def period(spec: dict) -> tuple[int, int, int]:
    """(layers per period, whole periods, Mamba2 layers left over): a
    period is ``shared_attn_every`` Mamba2 layers plus the shared block."""
    k = spec["shared_attn_every"]
    n = spec["n_layers"]
    return k + 1, n // k, n % k


def layer_order(spec: dict) -> list[tuple[str, object]]:
    """The model's layers in order: ("mamba", (slot, rep)) for a stacked
    Mamba2 layer, ("rest", i) for a leftover one, ("shared", None) for an
    application of the shared block."""
    pi, reps, rem = period(spec)
    out: list[tuple[str, object]] = []
    for r in range(reps):
        for j in range(pi):
            if j == pi - 1:
                out.append(("shared", None))
            else:
                out.append(("mamba", (j, r)))
    out += [("rest", i) for i in range(rem)]
    return out


def _shared_shapes(spec: dict) -> dict:
    d, D = spec["d_model"], spec["head_dim"]
    H, KV, F_ = spec["n_heads"], spec["n_kv_heads"], spec["d_ff"]
    return {"ln1": {"scale": (d,)},
            "attn": {"wq": (d, H * D), "wk": (d, KV * D), "wv": (d, KV * D),
                     "wo": (H * D, d)},
            "ln2": {"scale": (d,)},
            "mlp": {"gate": (d, F_), "up": (d, F_), "down": (F_, d)}}


def shapes(spec: dict, embed_rows: int) -> dict:
    """The tree of leaf shapes."""
    pi, reps, rem = period(spec)
    mamba = W.mamba_shapes(spec, d_inner(spec))
    blocks = {f"slot{j}": {} if j == pi - 1 else W.stacked(mamba, reps)
              for j in range(pi)}
    return {"embed": (embed_rows, spec["d_model"]),
            "final_norm": {"scale": (spec["d_model"],)},
            "blocks": blocks,
            "rest": [mamba] * rem,
            "shared_attn": _shared_shapes(spec)}


# -- the plain reference ------------------------------------------------------

def shared_block(p, x, spec, mode):
    """Pre-norm causal attention + pre-norm GeGLU MLP, both residual."""
    eps = spec["norm_eps"]
    x = x + ref.attention(p["attn"], ref.rmsnorm(x, p["ln1"]["scale"], eps),
                          spec, mode)
    m = p["mlp"]
    h = ref.rmsnorm(x, p["ln2"]["scale"], eps)
    g = jax.nn.gelu(ref.einsum(mode, "bsd,df->bsf", h, m["gate"]),
                    approximate=False)
    u = ref.einsum(mode, "bsd,df->bsf", h, m["up"])
    return x + ref.einsum(mode, "bsf,fd->bsd", g * u, m["down"])


def _layers(params, spec):
    for kind, where in layer_order(spec):
        if kind == "shared":
            yield shared_block, params["shared_attn"]
        else:
            yield ref.mamba_layer, W.layer_weights(params, (kind, where))


def hidden(params, tokens, spec: dict, mode: str = "f32"):
    """Final hidden states (B,S,d) float32 of right-padded ``tokens``."""
    return ref.hidden(params["embed"], tokens, _layers(params, spec), spec,
                      mode)


logits_at = ref.tied_logits_at


# -- operations and bytes -----------------------------------------------------

def _counts(spec: dict) -> tuple[int, int]:
    """(Mamba2 layers, shared-block applications)."""
    order = layer_order(spec)
    return (sum(k != "shared" for k, _ in order),
            sum(k == "shared" for k, _ in order))


def shared_matmul_params(spec: dict) -> int:
    return F.attn_matmul_params(spec) + F.gated_mlp_params(spec)


def body_flops_per_token(spec: dict) -> int:
    """Every layer's matmuls and state updates, attention scores aside."""
    n_mamba, n_shared = _counts(spec)
    di = d_inner(spec)
    return (n_mamba * (2 * F.mamba_matmul_params(spec, di)
                       + F.ssm_flops_per_token(spec, di))
            + n_shared * 2 * shared_matmul_params(spec))


def prefill_flops(spec: dict, S: int) -> int:
    """A prompt of S tokens, logits for its last position."""
    _, n_shared = _counts(spec)
    return (S * body_flops_per_token(spec)
            + n_shared * F.causal_attn_flops(spec, S) + F.head_flops(spec))


def decode_flops(spec: dict, pos: int) -> int:
    """One decoded token at position ``pos`` (it sees pos + 1 keys)."""
    _, n_shared = _counts(spec)
    return (body_flops_per_token(spec)
            + n_shared * F.attn_flops(spec, pos + 1) + F.head_flops(spec))


def weight_bytes(spec: dict, itemsize: int = 2) -> int:
    """Bytes of the weights one decode step reads: the shared block once,
    and the tied head's ``vocab_size`` rows."""
    n_mamba, n_shared = _counts(spec)
    shared = shared_matmul_params(spec) if n_shared else 0
    return itemsize * (n_mamba * F.mamba_weight_params(spec, d_inner(spec))
                       + shared + spec["vocab_size"] * spec["d_model"])


def smoke(config: dict) -> dict:
    """Test size: the shared block after every second Mamba2 layer."""
    return dict(config, shared_attn_every=2)
