#!/usr/bin/env python3
"""Drive the system's main path once on a TPU and check what comes out.

    python3 chip_smoke.py              # one v5e chip: serve, then train
    python3 chip_smoke.py --chips 4    # a v5e:2x2 host: locality vs XLA

The model is the published ``zamba2-1.2b`` (38 Mamba2 layers plus a
weight-shared attention block every 6; d_model 2048, vocab 32000) with
random weights made from ``--seed``.

One chip:
  * serve — the full-depth config through ``Engine`` / ``Request`` /
    ``drain``. The last decode step's logits (through the KV and SSM
    caches) are checked against a cache-free ``transformer.forward`` over
    the same tokens.
  * train — the depth cut to two whole periods (12 Mamba2 layers plus the
    shared block; every width kept) through ``Trainer``, at seq_len 2048;
    every step's loss must be finite.

``--chips 4`` runs only the four-chip phase on a ('pod','data') = (2, 2)
mesh: the cut train step with ``grad_sync="locality"`` (FSDP) against
``grad_sync="xla"``, and sequence-sharded batch-1 decode with the
locality cache combine (the Pallas ``decode_stats`` kernel) against XLA's.

Every phase is a function of a config and a mesh, so the tests run them on
the CPU at the smoke preset; only :func:`main` demands a TPU. The last
line of stdout is one JSON object naming the device; any failed check
exits non-zero before it is printed.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

import jax                                         # noqa: E402
import jax.numpy as jnp                            # noqa: E402
import numpy as np                                 # noqa: E402

from repro import configs, telemetry               # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import mesh_from_devices    # noqa: E402
from repro.models import transformer               # noqa: E402

ARCH = "zamba2-1.2b"
TRAIN_LAYERS = 12        # two whole periods: 6 Mamba2 layers + shared block
# Sequences of 2048 per one-chip train step: the largest batch tried that
# compiles for a v5e chip. Batch 10 is refused ("Used 17.83G of 15.75G
# hbm"); batches 3, 5, 6 and 8 run, and peak_bytes_in_use reads 10.57 GB
# after the steps at each of them (5.29 GB before the first; the state
# of f32 params and Adam moments is 5.28 GB). Time per sequence: 0.122,
# 0.138, 0.146 and 0.111 s, so batch 8 is also the fastest per sequence.
TRAIN_BATCH = 8
# Serve check: max|decode - reference| / max|reference| over the logits of
# each request's last decode step. Both sides run the same bf16 weights but
# reach the logits differently — the recurrent SSM step against the chunked
# SSD scan, attention over the cache against full causal attention — so
# bf16's 2^-8 rounding enters each of the 44 residual layers in another
# order. At this depth (widths 128 and 512, on the CPU) that measured
# 0.04-0.08, while the same check against the logits one position early
# (an off-by-one cache) or of another request measured 1.2-1.5. 0.25 sits
# 3x above the first and 5x below the second.
SERVE_TOL = 0.25
# Four-chip checks: the locality and XLA paths compute the same function
# and differ only in reduction order (f32 partial stats combined across 4
# shards; gradients reduce-scattered in another association), which bf16
# then carries through every later layer. Each limit sits between the
# sound reading and the reading with a fault planted in the locality path
# (tests/test_chip_smoke.py plants both faults at the smoke preset on four
# CPU devices).
# Train loss, absolute, on losses near ln(32000) ~ 10.4: sound 2.17e-4 on
# a v5e:2x2 at published widths (2.8e-4 on CPU); a grad sync with no
# inter-pod exchange reads 2.2e-2 on CPU.
LOSS_AGREE_TOL = 2e-3
# Decode logits, the SERVE_TOL metric: sound 0.027 on the v5e:2x2 (0.0099
# on CPU); a combine that drops the partial stats of the shard holding the
# prompt reads 0.76 on CPU. Dropping the shard that holds only the newest
# 1-4 tokens reads 0.089, within 3.3x of the chip's sound reading: too
# close to bf16's noise for a limit to tell apart.
DECODE_AGREE_TOL = 0.1


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class SmokeFailure(RuntimeError):
    """A phase produced output that fails its check."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def span_seconds(tracer: telemetry.Tracer, name: str) -> list[float]:
    """Durations of every closed span called ``name``, in seconds."""
    opened: dict = {}
    out = []
    for ev in tracer.events():
        if ev.get("name") != name:
            continue
        lane = ev["tid"]
        if ev["ph"] == "B":
            opened.setdefault(lane, []).append(ev["ts"])
        elif ev["ph"] == "E":
            out.append((ev["ts"] - opened[lane].pop()) / 1e6)
    return out


def memory(device) -> dict:
    """Device memory counters where the backend reports them."""
    stats = device.memory_stats() or {}
    return {k: stats[k] for k in ("peak_bytes_in_use", "bytes_in_use",
                                  "bytes_limit") if k in stats}


def rel_err(a, b) -> float:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def greedy(logits, vocab_size: int) -> jax.Array:
    """(B, 1) next tokens: argmax over the real (unpadded) vocabulary."""
    return jnp.argmax(logits[:, -1, :vocab_size], axis=-1)[:, None].astype(
        jnp.int32)


def init_params(cfg, mesh, seed: int):
    """f32 params made on the device from ``seed``, replicated on ``mesh``."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P())
    return jax.jit(lambda k: transformer.init_params(k, cfg),
                   out_shardings=rep)(jax.random.PRNGKey(seed))


def reference_last_logits(params, cfg, seqs: list[np.ndarray]) -> np.ndarray:
    """Cache-free forward over each token sequence: (n, vocab) f32 logits at
    each sequence's last position. The sequences are right-padded to one
    length (a multiple of 128, so the SSD scan keeps its chunking); every
    layer is causal, so padding never reaches an earlier position."""
    n = max(len(s) for s in seqs)
    width = -(-n // 128) * 128
    toks = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    last = np.asarray([len(s) - 1 for s in seqs], np.int32)

    @jax.jit
    def fwd(p, t, idx):
        logits, _, _ = transformer.forward(p, cfg, t, mode="train",
                                           remat=False)
        return logits[jnp.arange(t.shape[0]), idx].astype(jnp.float32)

    out = fwd(params, jnp.asarray(toks), jnp.asarray(last))
    return np.asarray(out)[:, :cfg.vocab_size]


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def serve_phase(cfg, mesh, *, prompt_lens=(128, 256, 384, 512),
                max_new: int = 32, seed: int = 0, tol: float = SERVE_TOL
                ) -> dict:
    """Continuous-batching greedy decode of one request per prompt length;
    the last decode step's logits checked against a cache-free forward."""
    from repro.serve import Engine, Request, ServeSpec
    tracer = telemetry.Tracer(jax_annotations=False)
    registry = telemetry.MetricsRegistry()
    dev = mesh.devices.flat[0]
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in prompt_lens]
    page = 16
    cache_len = -(-(max(prompt_lens) + max_new) // page) * page
    spec = ServeSpec(batch=len(prompts), cache_len=cache_len, page_len=page)
    with jax.set_mesh(mesh):
        t0 = time.perf_counter()
        params = init_params(cfg, mesh, seed)
        eng = Engine(cfg, mesh, params, spec, tracer=tracer,
                     registry=registry, log=log)
        del params                         # the engine holds its bf16 copy
        sched = eng.scheduler              # AOT-compiles the decode step
        jax.block_until_ready(eng.params)
        setup_s = time.perf_counter() - t0
        log(f"serve: combine={eng.combine.algorithm} "
            f"({eng.combine.source}), fused_stats={eng.art.fused_stats}, "
            f"batch={spec.batch}, cache_len={cache_len}")
        rids = [eng.submit(Request(tokens=p, max_new=max_new))
                for p in prompts]
        t0 = time.perf_counter()
        results = eng.drain()
        drain_s = time.perf_counter() - t0
        last = np.asarray(sched.last_logits, np.float32)[:, 0,
                                                          :cfg.vocab_size]
        seqs = [np.concatenate([p, results[r].tokens[:-1]])
                for p, r in zip(prompts, rids)]
        ref = reference_last_logits(eng.params, cfg, seqs)
    errs = []
    for i, r in enumerate(rids):
        res = results[r]
        check(res.finish_reason == "length" and res.n_tokens == max_new,
              f"request {r}: {res.finish_reason}, {res.n_tokens} tokens")
        got = last[res.slot]
        check(bool(np.all(np.isfinite(got))), f"request {r}: non-finite "
              "decode logits")
        errs.append(rel_err(got, ref[i]))
    err = max(errs)
    prefill = span_seconds(tracer, "serve/prefill")
    decode = span_seconds(tracer, "serve/decode_step")
    out = {
        "arch": cfg.name, "n_layers": cfg.n_layers,
        "params": cfg.param_count(), "requests": len(rids),
        "prompt_lens": list(prompt_lens), "max_new": max_new,
        "setup_s": setup_s, "drain_s": drain_s,
        "compile_s": sum(span_seconds(tracer, "serve/compile")),
        "prefill_s": sum(prefill), "prefill_calls": len(prefill),
        "decode_s": sum(decode), "decode_steps": len(decode),
        "decode_step_median_s": float(np.median(decode)) if decode else None,
        "logit_rel_err": err, "logit_rel_errs": errs, "tol": tol,
        "memory": memory(dev),
    }
    log("serve: " + json.dumps(out))
    check(err <= tol, f"decode logits off the cache-free reference: "
          f"{err:.3g} > {tol}")
    return out


def train_phase(cfg, mesh, *, seq_len: int, global_batch: int, steps: int,
                ckpt_dir: Path, grad_sync: str = "locality", seed: int = 0
                ) -> dict:
    """A few ``Trainer`` steps from scratch; every loss must be finite."""
    from repro.train import Trainer, TrainerConfig
    tracer = telemetry.Tracer(jax_annotations=False)
    registry = telemetry.MetricsRegistry()
    dev = mesh.devices.flat[0]
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    tcfg = TrainerConfig(steps=steps, seq_len=seq_len,
                         global_batch=global_batch, ckpt_dir=str(ckpt_dir),
                         ckpt_every=steps, log_every=1, grad_sync=grad_sync,
                         seed=seed)
    with jax.set_mesh(mesh):
        t0 = time.perf_counter()
        tr = Trainer(cfg, mesh, tcfg, log=log, tracer=tracer,
                     registry=registry)
        setup_s = time.perf_counter() - t0
        before_fit = memory(dev)
        art = tr.artifacts
        log(f"train: grad_sync={art.grad_sync} ({art.grad_algorithm}, "
            f"{art.grad_sync_source}), prefetch_depth={art.prefetch_depth}")
        t0 = time.perf_counter()
        run = tr.fit(resume="none")
        run_s = time.perf_counter() - t0
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    losses = [m["loss"] for m in tr.metrics_history]
    dts = [m["dt"] for m in tr.metrics_history]
    out = {
        "arch": cfg.name, "n_layers": cfg.n_layers,
        "params": cfg.param_count(), "seq_len": seq_len,
        "global_batch": global_batch, "steps": len(losses),
        "setup_s": setup_s, "run_s": run_s,
        "compile_s": registry.gauge("train/compile_time_s").value,
        "step_s": dts,
        "step_median_s": float(np.median(dts[1:] or dts)) if dts else None,
        "losses": losses, "memory_before_fit": before_fit,
        "memory": memory(dev),
    }
    log("train: " + json.dumps(out))
    check(run["status"] == "complete" and len(losses) == steps,
          f"trainer ended {run['status']} after {len(losses)} steps")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    return out


def four_chip_phase(cfg_train, cfg_serve, mesh, *, seq_len: int,
                    global_batch: int, steps: int, prompt_len: int,
                    cache_len: int, decode_steps: int,
                    fused_stats: str = "auto", seed: int = 0) -> dict:
    """Locality against XLA on a multi-device mesh: the FSDP train step's
    losses, and teacher-forced sequence-sharded decode logits."""
    from repro.data import SyntheticLM
    from repro.serve import Engine, ServeSpec
    from repro.train.step import custom_batch_specs, init_state, \
        make_train_step

    out: dict = {"mesh": dict(zip(mesh.axis_names, mesh.devices.shape))}
    dev = mesh.devices.flat[0]
    data = SyntheticLM(vocab_size=cfg_train.vocab_size, seq_len=seq_len,
                       global_batch=global_batch, seed=seed)
    bspec = custom_batch_specs(cfg_train, global_batch, seq_len)
    losses: dict[str, list[float]] = {}
    with jax.set_mesh(mesh):
        for mode in ("locality", "xla"):
            art = make_train_step(cfg_train, mesh, grad_sync=mode, fsdp=True,
                                  shape=bspec)
            state = init_state(cfg_train, mesh, art, seed=seed)
            losses[mode], times = [], []
            for step in range(steps):
                batch = {k: jax.device_put(v, art.batch_shardings[k])
                         for k, v in data.batch(step).items()}
                t0 = time.perf_counter()
                state, metrics = art.step_fn(state, batch)
                losses[mode].append(float(metrics["loss"]))
                times.append(time.perf_counter() - t0)
            # the first call compiles
            out[f"train_{mode}_step_s"] = times
            del state
    out["train_losses"] = losses
    loss_err = max(abs(a - b) for a, b in zip(losses["locality"],
                                              losses["xla"]))
    out["train_loss_abs_err"] = loss_err

    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg_serve.vocab_size, (1, prompt_len),
                          dtype=np.int32)
    with jax.set_mesh(mesh):
        params = init_params(cfg_serve, mesh, seed)
        eng = Engine(cfg_serve, mesh, params,
                     ServeSpec(batch=1, cache_len=cache_len,
                               combine="locality", fused_stats=fused_stats),
                     comm_telemetry=False, log=log)
        del params
        art = eng.art
        check(art.combine.algorithm == "locality",
              f"decode combine resolved to {art.combine}")
        log(f"decode: combine={art.combine.algorithm} over "
            f"p={art.combine.p} (p_local={art.combine.p_local}), "
            f"fused_stats={art.fused_stats}, seq_axes={art.seq_axes}")
        toks = jax.device_put(jnp.asarray(prompt), art.tok_sharding)
        logits, cache_l = art.prefill_fn(eng.params, {"tokens": toks})
        _, cache_x = art.prefill_fn(eng.params, {"tokens": toks})
        errs, t_loc, t_xla = [], [], []
        for _ in range(decode_steps):
            tok = jax.device_put(greedy(logits, cfg_serve.vocab_size),
                                 art.tok_sharding)
            t0 = time.perf_counter()
            lg_l, cache_l = art.decode_fn_locality(eng.params, cache_l, tok)
            lg_l = np.asarray(lg_l, np.float32)
            t1 = time.perf_counter()
            logits, cache_x = art.decode_fn_xla(eng.params, cache_x, tok)
            lg_x = np.asarray(logits, np.float32)
            t_xla.append(time.perf_counter() - t1)
            t_loc.append(t1 - t0)
            check(bool(np.all(np.isfinite(lg_l))), "non-finite decode logits")
            errs.append(rel_err(lg_l[..., :cfg_serve.vocab_size],
                                lg_x[..., :cfg_serve.vocab_size]))
    out.update(decode_rel_errs=errs, decode_rel_err=max(errs),
               decode_locality_step_s=t_loc, decode_xla_step_s=t_xla,
               fused_stats=art.fused_stats, memory=memory(dev),
               tol={"loss_abs": LOSS_AGREE_TOL, "logits": DECODE_AGREE_TOL})
    log("four-chip: " + json.dumps(out))
    failed = [msg for ok, msg in (
        (all(math.isfinite(x) for v in losses.values() for x in v),
         f"non-finite loss {losses}"),
        (loss_err <= LOSS_AGREE_TOL, f"locality and xla train losses "
         f"disagree by {loss_err:.3g} > {LOSS_AGREE_TOL}"),
        (max(errs) <= DECODE_AGREE_TOL, f"locality and xla decode logits "
         f"disagree by {max(errs):.3g} > {DECODE_AGREE_TOL}"),
    ) if not ok]
    check(not failed, "; ".join(failed))
    return out


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=Path, default=REPO / ".chip_smoke",
                    help="scratch directory (the trainer's checkpoints)")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] needs a TPU; JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"[chip_smoke] --chips {args.chips} needs {args.chips} "
              f"devices; JAX found {len(devices)}", file=sys.stderr)
        return 2
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {enable_compile_cache()}")
    # "auto" choices come from the policy's model fallback, never from a
    # tuning table left on disk by an earlier run
    from repro.tuning.policy import Policy, set_default_policy
    set_default_policy(Policy())

    cfg = configs.get(ARCH)
    cut = dataclasses.replace(cfg, n_layers=TRAIN_LAYERS)
    log(f"{ARCH}: {cfg.param_count()} params, {cfg.n_layers} Mamba2 "
        f"layers + shared attention every {cfg.shared_attn_every}; train "
        f"cut to n_layers={TRAIN_LAYERS} ({cut.param_count()} params), "
        f"every width kept")
    mesh = mesh_from_devices(devices[:args.chips])
    t0 = time.perf_counter()
    if args.chips == 1:
        serve_phase(cfg, mesh, seed=args.seed)
        gc.collect()          # engine <-> scheduler cycle holds the weights
        log(f"after serve: {memory(dev)}")
        train_phase(cut, mesh, seq_len=2048, global_batch=TRAIN_BATCH,
                    steps=4, ckpt_dir=args.out / "train_ckpt",
                    seed=args.seed)
    else:
        four_chip_phase(cut, cfg, mesh, seq_len=2048, global_batch=8,
                        steps=3, prompt_len=1024, cache_len=8192,
                        decode_steps=8, seed=args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
