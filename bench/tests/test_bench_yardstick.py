"""The yardstick's arithmetic: peaks, model FLOPs, and the trace reduction
(on hand-made events, and on an excerpt of a trace taken on a v5e)."""
from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import pytest

from bench import cells, flops, record, trace_reduce
from bench.cells import load_cell

EXCERPT = Path(__file__).resolve().parent / "data" / "v5e_chat_excerpt.json"
# every cell of the benchmark, so that a configuration added by files gets
# these tests with no edit
CELLS = tuple(w["name"] for w in json.loads(
    (Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text()
)["workloads"])


def test_peaks_are_keyed_by_device_kind():
    assert flops.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert flops.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peak("cpu")


def test_flops_by_hand_at_a_tiny_size():
    """Zamba2's family (a Mamba2 trunk and a shared attention block)."""
    spec = dict(load_cell("zamba2-1.2b.chat").config, n_layers=2,
                shared_attn_every=2, d_model=4, ssm_expand=2, ssm_headdim=4,
                ssm_state=2, ssm_ngroups=1, ssm_conv=3, n_heads=2,
                n_kv_heads=1, head_dim=2, d_ff=5, vocab_size=10)
    fam = cells.family(spec)
    # mamba: d_inner 8, 2 heads of 4; in_proj 4 x (16 + 4 + 2), out 8 x 4
    mamba_mm = 4 * 22 + 8 * 4
    ssm = 5 * 2 * 2 * 4 + 2 * 3 * (8 + 4)
    shared_mm = 2 * 4 * 4 + 2 * 4 * 2 + 3 * 4 * 5
    body = 2 * (2 * mamba_mm + ssm) + 2 * shared_mm
    assert fam.body_flops_per_token(spec) == body
    head = 2 * 10 * 4
    # three tokens: the shared block's one query per token sees 1, 2, 3 keys
    assert fam.prefill_flops(spec, 3) == 3 * body + 4 * 2 * 2 * 6 + head
    assert fam.decode_flops(spec, 5) == body + 4 * 2 * 2 * 6 + head


@pytest.mark.parametrize("name", CELLS)
def test_weights_counted_agree_with_the_programs_parameter_count(name):
    """The weights the byte count reads (every leaf of two dimensions or
    more, the stacking axis aside), plus the vectors it leaves out (norms,
    biases, decays), the vocabulary's padding rows and, where the head is
    a matrix of its own, the embedding table (a decode step reads only
    the rows of its tokens), are every parameter the program counts."""
    from bench.drivers.serve import program_config
    spec = load_cell(name).config
    cfg = program_config(spec)
    fam = cells.family(spec)
    tree = fam.shapes(spec, cfg.padded_vocab)
    vectors = sum(math.prod(shape) for path, shape in
                  jax.tree_util.tree_flatten_with_path(
                      tree, is_leaf=lambda s: isinstance(s, tuple))[0]
                  if len(shape) - (path[0].key == "blocks") == 1)
    d, V = spec["d_model"], spec["vocab_size"]
    untied = "head" in tree
    pad = (cfg.padded_vocab - V) * d * (1 + untied)
    assert fam.weight_bytes(spec, itemsize=1) + vectors \
        + pad + untied * V * d == cfg.param_count()


@pytest.mark.parametrize("name", CELLS)
def test_mfu_counts_the_cells_family(name):
    """``mfu`` reads the FLOP counts of the cell's family: one prefill and
    the two tokens decoded in the window, over the time in their spans."""
    spec = load_cell(name).config
    fam = cells.family(spec)
    peak = flops.peak("TPU v5 lite")
    spans = [{"name": "serve/prefill", "t0": 1.0, "t1": 1.02,
              "args": {"prompt_len": 300}},
             {"name": "serve/decode_step", "t0": 1.03, "t1": 1.06,
              "args": {}},
             {"name": "serve/decode_step", "t0": 1.07, "t1": 1.10,
              "args": {}}]
    req = record.Request(arrival=0.9, prompt_len=300,
                         times=[1.02, 1.06, 1.10, 2.5], reason="length")
    run = record.Run(spec=spec, seconds=1.0, setup_s=1.0, t_open=1.0,
                     t_close=2.0, requests=[req], spans=spans, peak=peak)
    work = (fam.prefill_flops(spec, 300) + fam.decode_flops(spec, 300)
            + fam.decode_flops(spec, 301))
    bench = Path(__file__).resolve().parents[1]
    assert cells.reader(bench, "mfu")(run) == pytest.approx(
        100 * work / (0.08 * peak["bf16_flops_per_s"]))


def _ev(plane, line, name, a, b):
    return {"plane": plane, "line": line, "name": name, "start_ns": a,
            "dur_ns": b - a}


def test_reduce_on_hand_made_events():
    d = "/device:TPU:0"
    ev = [_ev("/host:CPU", "python", "bench/window", 0, 100),
          _ev("/host:CPU", "python", "serve/decode_step", 0, 20),
          _ev("/host:CPU", "python", "serve/prefill", 62, 100),
          _ev(d, "XLA Ops", "fusion.1", 10, 30),
          _ev(d, "XLA Ops", "fusion.2", 25, 40),          # overlaps fusion.1
          _ev(d, "XLA Ops", "all-reduce.3", 35, 50),      # 10 ns exposed
          _ev(d, "XLA Ops", "fusion.1", 80, 90),
          _ev(d, "XLA Ops", "fusion.9", 120, 130)]        # after the window
    r = trace_reduce.reduce(ev)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(50e-9)            # 10-50, 80-90
    assert r["idle_share"] == pytest.approx(0.5)
    assert r["collective_exposed_s"] == pytest.approx(10e-9)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(30e-9)]
    # idle 0-10 in the decode span, 50-80 and 90-100 in the prefill's
    assert dict(r["idle_gaps"]) == {"serve/decode_step": pytest.approx(10e-9),
                                    "serve/prefill": pytest.approx(40e-9)}
    bare = trace_reduce.reduce([ev[0]] + ev[3:])
    assert dict(bare["idle_gaps"]) == {"no host span": pytest.approx(50e-9)}
    assert trace_reduce.reduce(ev[1:]) is None            # no window


def test_reduce_on_a_recorded_v5e_excerpt():
    events = json.loads(EXCERPT.read_text())
    r = trace_reduce.reduce(events)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["window_s"] == pytest.approx(0.025)
    assert r["device_ops"] and r["idle_gaps"]
    assert sum(s for _, s in r["idle_gaps"]) <= r["window_s"] - r["busy_s"] \
        + 1e-12
