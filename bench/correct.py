"""``correct`` for a served model: are the served tokens the reference's?

Greedy decoding serves the highest logit. For a sample of the finished
requests, the float32 reference runs once over each prompt followed by
its served tokens, and at every served position reads how far the served
token's logit lies below the reference's best there. The widest such gap
over the sample is compared with the cell's limit. A sound bf16 program
serves the reference's choice or one within rounding of it; serving from
a lower precision, a stale cache or another request's state opens the gap.

The sample is drawn from the seed and always holds the request that
served the most tokens.
"""
from __future__ import annotations

import numpy as np

from bench.reference import model as ref

GAP = "max_logit_gap"
CHUNK = 512            # positions whose logits are held at once


def sample(requests: list, k: int, seed: int) -> list:
    done = [r for r in requests if r.reason == "length"]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i].tokens))
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng(int(seed))
    pick = rng.choice(rest, size=min(k - 1, len(rest)), replace=False)
    return [done[longest]] + [done[i] for i in sorted(pick)]


def _batch(reqs: list, width: int):
    """Token batch of prompt + served tokens (but the last), right-padded
    to ``width`` (one shape, so one compile, for every run), and the (row,
    position) of every served token's prediction."""
    seqs = [np.concatenate([r.prompt, r.tokens[:-1]]).astype(np.int32)
            for r in reqs]
    toks = np.zeros((len(seqs), width), np.int32)
    rows, cols, served = [], [], []
    for i, (r, s) in enumerate(zip(reqs, seqs)):
        toks[i, :len(s)] = s
        P = len(r.prompt)
        rows += [i] * len(r.tokens)
        cols += list(range(P - 1, P - 1 + len(r.tokens)))
        served += [int(t) for t in r.tokens]
    return toks, np.asarray(rows), np.asarray(cols), np.asarray(served)


def gaps(params, spec: dict, reqs: list, width: int, *,
         control: bool = False) -> np.ndarray:
    """Per served position: the reference's best logit minus the logit of
    the served token, or with ``control`` of the token that the fp8
    control puts first."""
    import jax.numpy as jnp
    toks, rows, cols, served = _batch(reqs, width)
    x = ref.hidden(params, jnp.asarray(toks), spec, "f32")
    xc = ref.hidden(params, jnp.asarray(toks), spec, "fp8") if control \
        else None
    out = []
    for a in range(0, len(rows), CHUNK):
        sl = slice(a, a + CHUNK)
        lg = np.asarray(ref.logits_at(params, x, rows[sl], cols[sl], spec))
        if control:
            pick = np.asarray(ref.logits_at(params, xc, rows[sl], cols[sl],
                                            spec, "fp8")).argmax(-1)
        else:
            pick = served[sl]
        out.append(lg.max(-1) - lg[np.arange(len(pick)), pick])
    return np.concatenate(out)


def check_serve(params, spec: dict, requests: list, limits: dict, k: int,
                seed: int, width: int, *, control: bool = False
                ) -> tuple[bool, dict]:
    """(within every limit, {name: {"value", "limit"}}); ``width`` is the
    longest prompt + output the traffic allows; ``control`` adds the fp8
    control's reading on the same sample."""
    reqs = sample(requests, k, seed) if k else []
    limit = limits.get(GAP)
    if not reqs:
        return False, {GAP: {"value": None, "limit": limit}}
    g = float(gaps(params, spec, reqs, width).max())
    out = {"value": g, "limit": limit,
           "positions": int(sum(len(r.tokens) for r in reqs))}
    if control:
        out["control"] = float(gaps(params, spec, reqs, width,
                                    control=True).max())
    return limit is not None and g <= limit, {GAP: out}
