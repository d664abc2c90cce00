"""End-to-end driver: train a ~100M-parameter llama-family model for a few
hundred steps on a multi-device host mesh, with the paper's locality-aware
gradient sync, checkpoints, and straggler monitoring.

    PYTHONPATH=src python examples/train_100m.py --steps 300

(CPU-bound: ~1-3 s/step. Use --steps 30 for a quick look; the loss curve is
written to results/train_100m_loss.csv either way.)

With ``--preemptible`` the run goes through the fleet controller
(DESIGN.md §11): SIGTERM becomes a graceful drain-and-commit instead of
lost work (send ``kill -TERM <pid>`` while it trains and watch the
resume), the ('pod','data') mesh is chosen pod-aligned by the cost
model, and a hard kill restarts from the committed step with the
bounded retry -> shrink -> halt escalation.
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--grad-sync", default="locality")
    ap.add_argument("--preemptible", action="store_true",
                    help="run under the FleetController: SIGTERM drains "
                         "gracefully, kills resume from the committed step")
    ap.add_argument("--pod-size", type=int, default=4,
                    help="physical pod width for --preemptible layout "
                         "selection")
    args = ap.parse_args()

    os.environ.setdefault(
        "XLA_FLAGS",
        f"--xla_force_host_platform_device_count={args.devices}")
    import dataclasses

    import jax
    import numpy as np

    from repro import configs
    from repro.launch.mesh import make_mesh
    from repro.models import transformer
    from repro.train import Trainer, TrainerConfig

    # ~100M params: 12L, d=768, heads 12, ff 3072, vocab 32k (GPT-2-small-ish
    # dims in the llama3 family).
    cfg = dataclasses.replace(
        configs.get("llama3.2-3b"), name="llama-100m",
        n_layers=12, d_model=768, n_heads=12, n_kv_heads=4, head_dim=64,
        d_ff=3072, vocab_size=32_000)
    a = jax.eval_shape(lambda k: transformer.init_params(k, cfg),
                       jax.random.PRNGKey(0))
    n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(a))
    print(f"[train_100m] {n/1e6:.1f}M params, {args.devices} devices, "
          f"grad_sync={args.grad_sync}")

    tcfg = TrainerConfig(steps=args.steps, seq_len=256, global_batch=8,
                         ckpt_dir="/tmp/repro_100m_ckpt", ckpt_every=100,
                         log_every=10, grad_sync=args.grad_sync, lr=3e-4)

    if args.preemptible:
        # fleet-controller path: pod-aligned ('pod','data') layout from
        # the cost model; SIGTERM chains into a graceful drain-and-commit
        # and the controller restarts any killed episode from the
        # committed step (ctrl-C still interrupts: SIGINT is untouched).
        from repro.fleet import FleetController
        from repro.runtime import PreemptionSignal

        def make_trainer(mesh):
            return Trainer(cfg, mesh, tcfg,
                           preemption=PreemptionSignal(install_sigterm=True))

        fc = FleetController(make_trainer, pod_size=args.pod_size,
                             devices=args.devices)
        report = fc.run()
        metrics = sorted(report.loss_by_step)
        rows = [(s, report.loss_by_step[s], 0.0) for s in metrics]
        final = report.loss_by_step[metrics[-1]] if metrics else float("nan")
        print(f"[train_100m] fleet run {report.status}: "
              f"{len(report.episodes)} episode(s), final layout "
              f"{report.final_layout}")
    else:
        mesh = make_mesh((2, args.devices // 4, 2),
                             ("pod", "data", "model"))
        jax.set_mesh(mesh)
        tr = Trainer(cfg, mesh, tcfg)
        out = tr.run()
        rows = [(m["step"], m["loss"], m["dt"]) for m in tr.metrics_history]
        final = out["final_loss"]

    os.makedirs("results", exist_ok=True)
    with open("results/train_100m_loss.csv", "w") as f:
        f.write("step,loss,dt\n")
        for step, loss, dt in rows:
            f.write(f"{step},{loss:.4f},{dt:.3f}\n")
    print(f"[train_100m] done: {final:.4f} "
          f"(loss curve -> results/train_100m_loss.csv)")


if __name__ == "__main__":
    main()
