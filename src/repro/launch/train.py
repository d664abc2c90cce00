"""Training launcher: the fault-tolerant ``Trainer`` loop on the devices
present.

``python -m repro.launch.train --arch zamba2-1.2b --ckpt-dir runs/z/ckpt``
runs the smoke preset of the architecture; ``--published`` takes the
published config (``configs.get``), and ``--n-layers`` cuts its depth while
keeping every width. The mesh is the DP mesh over the devices present
(``--devices N`` takes the first N; on the CPU it also forces N host
devices), or ``--mesh 2x2x2`` for a (pod, data, model) layout.
"""
import argparse
import dataclasses
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--published", action="store_true",
                    help="published config instead of the smoke preset")
    ap.add_argument("--n-layers", type=int, default=None,
                    help="cut the depth, keeping every width")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--mesh", default=None, help="e.g. 2x2x2 (pod,data,model)")
    ap.add_argument("--grad-sync", default="locality")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", required=True,
                    help="this run's checkpoints; an existing LATEST resumes")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[],
                    help="inject simulated failures at these steps")
    args = ap.parse_args()

    if args.devices:
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={args.devices}")
    import jax
    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import mesh_from_devices, parse_mesh
    from repro.runtime import FaultInjector
    from repro.train import Trainer, TrainerConfig

    enable_compile_cache()
    devs = jax.devices()[:args.devices] if args.devices else jax.devices()
    mesh = parse_mesh(args.mesh, devs) if args.mesh else mesh_from_devices(devs)
    jax.set_mesh(mesh)

    cfg = configs.get(args.arch) if args.published else configs.get_smoke(
        args.arch)
    if args.n_layers:
        cfg = dataclasses.replace(cfg, n_layers=args.n_layers)
    tcfg = TrainerConfig(
        steps=args.steps, seq_len=args.seq_len, global_batch=args.global_batch,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        grad_sync=args.grad_sync, lr=args.lr)
    trainer = Trainer(cfg, mesh, tcfg,
                      fault_injector=FaultInjector(tuple(args.fail_at)))
    out = trainer.run()
    print(f"[train] done: {out}")


if __name__ == "__main__":
    main()
