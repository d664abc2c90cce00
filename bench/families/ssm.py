"""The SSM family (Mamba2, arXiv:2405.21060): a stack of pre-norm Mamba2
layers and nothing else, so no KV cache; the output head is tied to the
embedding.

Layout (what ``repro.models.transformer`` consumes): ``embed`` (rows, d);
``final_norm``; ``blocks`` with one slot, every layer stacked in it;
``rest`` empty.
"""
from __future__ import annotations

from bench import flops as F, weights as W
from bench.reference import model as ref


def d_inner(spec: dict) -> int:
    return spec["ssm_expand"] * spec["d_model"]


def shapes(spec: dict, embed_rows: int) -> dict:
    """The tree of leaf shapes."""
    return {"embed": (embed_rows, spec["d_model"]),
            "final_norm": {"scale": (spec["d_model"],)},
            "blocks": {"slot0": W.stacked(W.mamba_shapes(spec, d_inner(spec)),
                                          spec["n_layers"])},
            "rest": []}


# -- the plain reference ------------------------------------------------------

def _layers(params, spec):
    for r in range(spec["n_layers"]):
        yield ref.mamba_layer, W.layer_weights(params, ("mamba", (0, r)))


def hidden(params, tokens, spec: dict, mode: str = "f32"):
    """Final hidden states (B,S,d) float32 of right-padded ``tokens``."""
    return ref.hidden(params["embed"], tokens, _layers(params, spec), spec,
                      mode)


logits_at = ref.tied_logits_at


# -- operations and bytes -----------------------------------------------------

def body_flops_per_token(spec: dict) -> int:
    """Every layer's matmuls and state updates."""
    di = d_inner(spec)
    return spec["n_layers"] * (2 * F.mamba_matmul_params(spec, di)
                               + F.ssm_flops_per_token(spec, di))


def prefill_flops(spec: dict, S: int) -> int:
    """A prompt of S tokens, logits for its last position."""
    return S * body_flops_per_token(spec) + F.head_flops(spec)


def decode_flops(spec: dict, pos: int) -> int:
    """One decoded token; with no attention its cost is the same at any
    position."""
    return body_flops_per_token(spec) + F.head_flops(spec)


def weight_bytes(spec: dict, itemsize: int = 2) -> int:
    """Bytes of the weights one decode step reads, the tied head's
    ``vocab_size`` rows among them."""
    return itemsize * (spec["n_layers"]
                       * F.mamba_weight_params(spec, d_inner(spec))
                       + spec["vocab_size"] * spec["d_model"])


def smoke(config: dict) -> dict:
    """Test size: the common cut is all there is to it."""
    return config
