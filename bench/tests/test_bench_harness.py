"""The harness on the CPU: traffic, discovery by name, the result line,
no compiles in the window, and ``correct`` turning false under faults.

Runs use ``bench.tests.smoke`` cells (every width shrunk, a short window);
``run_cell`` is the whole of a run but the look for a TPU.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import cells, record, run as R, traffic
from bench.tests.smoke import smoke_cell

ROOT = Path(__file__).resolve().parents[2]
CELLS = ("zamba2-1.2b.chat", "mamba2-780m.offline")
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def _run(cell, seed=2**31 + 5, seconds=1.5, control=False):
    compiles = record.CompileCounter()
    res, _ = R.run_cell(cell, seed=seed, seconds=seconds, trace=False,
                     devices=jax.devices(), t_start=time.perf_counter(),
                     device_kind="TPU v5 lite", compiles=compiles,
                     control=control)
    res["device"] = R.device_fields(jax.devices(), res["device"])
    return res, compiles.count


@pytest.mark.parametrize("name", CELLS)
def test_traffic_is_the_seeds_and_on_the_warmed_grid(name):
    mix = cells.load_cell(name).traffic
    a = traffic.generate(mix, 2**33 + 1, 40, 1000)
    b = traffic.generate(mix, 2**33 + 1, 40, 1000)
    c = traffic.generate(mix, 7, 40, 1000)
    key = lambda items: [(i.at_s, i.max_new, i.prompt.tolist())
                         for i in items]
    assert key(a) == key(b) and key(a) != key(c)
    # another seed: the same sizes in another order
    assert sorted(len(i.prompt) for i in a) == sorted(len(i.prompt)
                                                      for i in c)
    assert sorted(i.max_new for i in a) == sorted(i.max_new for i in c)
    grid = set(traffic.lengths(mix["prompt"]))
    assert {len(i.prompt) for i in a} <= grid
    assert all(it.at_s < 40 for it in a)
    assert all(mix["output"]["min"] <= i.max_new <= mix["output"]["max"]
               for i in a)


def test_new_config_traffic_and_metric_are_found_without_an_edit(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = tmp_path / "bench"
    conf = json.loads((b / "configs" / "zamba2-1.2b.json").read_text())
    conf.update(name="zamba2-half", n_layers=19)
    (b / "configs" / "zamba2-half.json").write_text(json.dumps(conf))
    mix = json.loads((b / "traffic" / "chat.json").read_text())
    mix["arrivals"]["rate_per_s"] = 1.0
    (b / "traffic" / "slow-chat.json").write_text(json.dumps(mix))
    (b / "metrics" / "requests_per_s.py").write_text(
        "def read(run):\n    return len(run.requests) / run.seconds\n")
    bj = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bj["configs"].append({"name": "zamba2-half", "source": "x",
                          "file": "bench/configs/zamba2-half.json",
                          "reduced": ["n_layers"], "why": "x"})
    bj["workloads"].append({"name": "zamba2-half.slow-chat",
                            "config": "zamba2-half", "traffic": "slow-chat",
                            "chips": 1, "why": "x"})
    bj["per_layer"].append({"name": "requests_per_s", "unit": "1/s",
                            "better": "higher", "source": "host_clock",
                            "layer": "serve scheduler",
                            "moves": "ttft_p95_ms",
                            "workloads": ["zamba2-half.slow-chat"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bj))
    cell = cells.load_cell("zamba2-half.slow-chat", root=tmp_path)
    assert cell.config["n_layers"] == 19
    assert cell.traffic["arrivals"]["rate_per_s"] == 1.0
    assert [m["name"] for m in cell.per_layer] == ["requests_per_s"]
    run = record.Run(spec=cell.config, seconds=2.0, setup_s=1.0, t_open=0.0,
                     t_close=2.0, requests=[None] * 6, spans=[], peak={})
    assert cells.reader(cell.bench, "requests_per_s")(run) == 3.0
    assert cells.driver(cell).run is not None


_BJ = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("metric", [m["name"] for m in _BJ["end_to_end"]
                                    + _BJ["per_layer"]])
def test_every_metric_has_a_reader(metric):
    """Each metric reads from its own file, or a metric split by cells
    (``decode_step_ms.chat``) from its base name's, and a run that holds
    nothing to read gives None, never 0."""
    run = record.Run(spec={}, seconds=2.0, setup_s=1.0, t_open=0.0,
                     t_close=2.0, requests=[], spans=[], peak={})
    value = cells.reader(ROOT / "bench", metric)(run)
    assert value is None or metric == "setup_s"


@pytest.mark.parametrize("where", ["repo", "bare"])
def test_command_exits_nonzero_without_a_tpu(where, tmp_path):
    root = ROOT
    if where == "bare":           # only BENCHMARK.json and the benchmark
        shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
        shutil.copytree(ROOT / "bench", tmp_path / "bench")
        root = tmp_path
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "zamba2-1.2b.chat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=root, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("name", CELLS)
def test_smoke_run_compiles_nothing_in_the_window(name):
    """A whole run, and the fp8 control read on the same sample: the
    program is within the limit, the control is not."""
    res, compiles = _run(smoke_cell(name), control=True)
    assert compiles == 0
    assert list(res) == KEYS
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    want = {m["name"] for m in cells.load_cell(name).end_to_end}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    c = res["checks"]["max_logit_gap"]
    assert c["value"] <= c["limit"] < c["control"]


# The faults a serving cell can have, planted under the timed path: the
# harness has to come out not correct for each.
def _stale_state(monkeypatch):
    from repro.serve import scheduler as S
    orig = S.Scheduler._build_decode

    def build(self):
        orig(self)
        fn = self._decode

        def decode(params, cache, toks):
            logits, _ = fn(params, jax.tree.map(jnp.copy, cache), toks)
            return logits, cache          # the state comes back unchanged
        self._decode = decode
    monkeypatch.setattr(S.Scheduler, "_build_decode", build)


def _half_batch(monkeypatch):
    from repro.serve import scheduler as S
    orig = S.Scheduler._build_decode

    def build(self):
        orig(self)
        fn = self._decode

        def decode(params, cache, toks):
            logits, cache = fn(params, cache, toks)
            # only the even rows are computed; odd rows get their neighbour's
            return jnp.repeat(logits[::2], 2, axis=0), cache
        self._decode = decode
    monkeypatch.setattr(S.Scheduler, "_build_decode", build)


def _altered_token(monkeypatch):
    from repro.serve import scheduler as S
    orig = S.Scheduler._next_token

    def next_token(self, logits):
        tok = orig(self, logits)
        return jnp.where(tok % 7 == 3, (tok + 1) % self.cfg.vocab_size, tok)
    monkeypatch.setattr(S.Scheduler, "_next_token", next_token)


@pytest.mark.parametrize("fault", [_stale_state, _half_batch,
                                   _altered_token])
def test_faults_under_the_timed_path_are_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res, _ = _run(smoke_cell("zamba2-1.2b.chat", rows=8), seed=11)
    assert res["correct"] is False
    c = res["checks"]["max_logit_gap"]
    assert c["value"] > c["limit"]


def test_backlog_window_that_ends_before_any_answer_still_checks():
    """A traced backlog window may close before any request is done: the
    run steps on until enough have finished for the check."""
    cell = smoke_cell("mamba2-780m.offline", cache_len=320,
                      output={"median": 150, "sigma": 0.2, "min": 120,
                              "max": 200})
    res, _ = _run(cell, seed=5, seconds=0.05)
    c = res["checks"]["max_logit_gap"]
    assert res["correct"] is True
    assert c["positions"] >= 3 * 120
