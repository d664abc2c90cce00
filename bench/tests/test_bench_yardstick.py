"""The yardstick's arithmetic: peaks, model FLOPs, and the trace reduction
(on hand-made events, and on an excerpt of a trace taken on a v5e)."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import flops, trace_reduce
from bench.cells import load_cell

EXCERPT = Path(__file__).resolve().parent / "data" / "v5e_chat_excerpt.json"


def test_peaks_are_keyed_by_device_kind():
    assert flops.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert flops.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peak("cpu")


def test_flops_by_hand_at_a_tiny_size():
    spec = {"family": "hybrid", "n_layers": 2, "shared_attn_every": 2,
            "d_model": 4, "ssm_expand": 2, "ssm_headdim": 4, "ssm_state": 2,
            "ssm_ngroups": 1, "ssm_conv": 3, "n_heads": 2, "n_kv_heads": 1,
            "head_dim": 2, "d_ff": 5, "vocab_size": 10}
    # mamba: d_inner 8, 2 heads of 4; in_proj 4 x (16 + 4 + 2), out 8 x 4
    mamba_mm = 4 * 22 + 8 * 4
    ssm = 5 * 2 * 2 * 4 + 2 * 3 * (8 + 4)
    shared_mm = 2 * 4 * 4 + 2 * 4 * 2 + 3 * 4 * 5
    body = 2 * (2 * mamba_mm + ssm) + 2 * shared_mm
    assert flops.body_flops_per_token(spec) == body
    head = 2 * 10 * 4
    # three tokens: the shared block's one query per token sees 1, 2, 3 keys
    assert flops.prefill_flops(spec, 3) == 3 * body + 4 * 2 * 2 * 6 + head
    assert flops.decode_flops(spec, 5) == body + 4 * 2 * 2 * 6 + head


@pytest.mark.parametrize("name", ["zamba2-1.2b.chat", "mamba2-780m.offline"])
def test_weights_counted_agree_with_the_programs_parameter_count(name):
    """The weights the FLOP count multiplies by, plus the vectors it leaves
    out (norms, biases, decays, the vocabulary's padding rows), are every
    parameter the program counts."""
    from bench import weights as W
    from bench.drivers.serve import program_config
    spec = load_cell(name).config
    cfg = program_config(spec)
    d = spec["d_model"]
    di, H, _, N, G = W.ssm_dims(spec)
    n_mamba = sum(k != "shared" for k, _ in W.layer_order(spec))
    vectors = n_mamba * ((di + 2 * G * N) + 3 * H + di + d) + d
    if spec["family"] == "hybrid":
        vectors += 2 * d
    pad_rows = (cfg.padded_vocab - spec["vocab_size"]) * d
    assert flops.weight_bytes(spec, itemsize=1) + vectors + pad_rows \
        == cfg.param_count()


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
