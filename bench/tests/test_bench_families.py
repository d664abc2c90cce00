"""Each model family's code is found by name, reads exactly as it did
before it moved into ``bench/families/``, and a new family enters by
adding files only.

``data/pinned_families.json`` holds numbers recorded from the harness as
it stood before the move (CPU, XLA's default flags): a SHA-256 of every
weight leaf and of the reference's hidden states and logits at test size,
and the FLOP and byte counts at the published sizes. The moved code has to
give them exactly.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells, weights as W
from bench.tests.smoke import smoke_cell

DATA = Path(__file__).resolve().parent / "data"
ROOT = Path(__file__).resolve().parents[2]
PINNED = json.loads((DATA / "pinned_families.json").read_text())
CONFIGS = sorted(PINNED["configs"])


def _sha(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(np.asarray(a)).tobytes()
                          ).hexdigest()


def _pinned(name: str):
    pin = PINNED["configs"][name]
    return pin, cells.family(pin["test_spec"])


def test_unknown_family_names_the_file_it_looked_for():
    with pytest.raises(KeyError, match=r"families/no-such\.py"):
        cells.family({"family": "no-such"})


@pytest.mark.parametrize("name", CONFIGS)
def test_pinned_test_size_is_the_smoke_cut(name):
    pin = PINNED["configs"][name]
    assert smoke_cell(pin["cell"]).config == pin["test_spec"]


@pytest.mark.parametrize("name", CONFIGS)
def test_pinned_weight_leaves(name):
    """The same tree from the seed, bit for bit, its leaves in the same
    order (so each gets the same PRNG key)."""
    pin, _ = _pinned(name)
    params = W.make(pin["test_spec"], PINNED["seed"], pin["embed_rows"])
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    got = [[jax.tree_util.keystr(p), list(leaf.shape), str(leaf.dtype),
            _sha(leaf)] for p, leaf in flat]
    assert got == pin["leaves"]


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("mode", ["f32", "fp8"])
def test_pinned_reference(name, mode):
    """The reference's hidden states and logits, and the fp8 control's."""
    pin, fam = _pinned(name)
    spec = pin["test_spec"]
    params = W.make(spec, PINNED["seed"], pin["embed_rows"])
    toks = np.random.default_rng(PINNED["tokens_seed"]).integers(
        0, spec["vocab_size"], pin["token_shape"], dtype=np.int32)
    x = fam.hidden(params, jnp.asarray(toks), spec, mode)
    lg = fam.logits_at(params, x, pin["rows"], pin["cols"], spec, mode)
    want = pin["reference"][mode]
    assert (_sha(x), _sha(lg)) == (want["hidden"], want["logits_at"])


@pytest.mark.parametrize("name", CONFIGS)
def test_pinned_flops_and_bytes(name):
    pin, fam = _pinned(name)
    spec = cells.load_cell(pin["cell"]).config
    want = pin["published"]
    assert {str(S): fam.prefill_flops(spec, S)
            for S in PINNED["lengths"]} == want["prefill_flops"]
    assert {str(p): fam.decode_flops(spec, p)
            for p in PINNED["lengths"]} == want["decode_flops"]
    assert fam.weight_bytes(spec) == want["weight_bytes"]


# a configuration of the dense family: yi-6b's published sizes
# (https://huggingface.co/01-ai/Yi-6B/blob/main/config.json)
YI_6B = {"name": "yi-6b", "source": "https://huggingface.co/01-ai/Yi-6B",
         "arch": "yi-6b", "family": "dense", "n_layers": 32, "d_model": 4096,
         "n_heads": 32, "n_kv_heads": 4, "head_dim": 128, "d_ff": 11008,
         "vocab_size": 64000, "tie_embeddings": False,
         "rope_theta": 5000000.0, "norm_eps": 1e-05, "dtype": "bfloat16",
         "weights_dtype": "bfloat16"}
NEW_CELL = "yi-6b.short-chat"


def _digests(root: Path) -> dict:
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_family_enters_by_adding_files_only(tmp_path):
    """A copy of the benchmark takes the dense family, a configuration of
    it, a traffic mix, a cell and an ``mfu`` metric for the cell, each a new
    file or a new entry. The copy's own per-configuration tests then run on
    the new cell: its weight tree is the program's, the program's prefill
    and decode agree with the family's reference, its loss and gradients
    with the reference's, its weights with the parameter count, and ``mfu``
    reads its FLOP counts."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path)
    b = tmp_path / "bench"
    shutil.copy(DATA / "dense.py", b / "families" / "dense.py")
    (b / "configs" / "yi-6b.json").write_text(json.dumps(YI_6B))
    mix = json.loads((b / "traffic" / "chat.json").read_text())
    mix["arrivals"]["rate_per_s"] = 2.0
    (b / "traffic" / "short-chat.json").write_text(json.dumps(mix))
    bj = json.loads((tmp_path / "BENCHMARK.json").read_text())
    old = json.loads(json.dumps(bj))
    bj["configs"].append({"name": "yi-6b", "source": YI_6B["source"],
                          "file": "bench/configs/yi-6b.json", "reduced": [],
                          "why": "x"})
    bj["workloads"].append({"name": NEW_CELL, "config": "yi-6b",
                            "traffic": "short-chat", "chips": 1, "why": "x"})
    bj["per_layer"].append({"name": "mfu.yi-6b", "unit": "%",
                            "better": "higher", "source": "program_span",
                            "layer": "whole step", "moves": "ttft_p95_ms",
                            "workloads": [NEW_CELL]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bj))

    after = _digests(tmp_path)
    changed = {p for p in before if after[p] != before[p]}
    assert changed == {Path("BENCHMARK.json")}
    assert all(bj[k][:len(v)] == v for k, v in old.items()
               if isinstance(v, list))
    cell = cells.load_cell(NEW_CELL, root=tmp_path)
    assert cell.config["family"] == "dense"
    assert [m["name"] for m in cell.per_layer] == ["mfu.yi-6b"]

    env = dict(os.environ, PYTHONPATH=f"{tmp_path}{os.pathsep}{ROOT / 'src'}")
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-k", NEW_CELL,
         "bench/tests/test_bench_reference.py",
         "bench/tests/test_bench_yardstick.py"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-4000:]
    assert "6 passed" in p.stdout, p.stdout[-4000:]
