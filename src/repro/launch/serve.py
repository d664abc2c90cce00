"""Serving launcher: continuous-batching greedy decoding on the devices
present.

``python -m repro.launch.serve --arch mamba2-780m --batch 8 --max-new 16``
runs the smoke preset; ``--published`` takes the published config
(``configs.get``). The mesh is the DP mesh over the devices present
(``--devices N`` takes the first N; on the CPU it also forces N host
devices).
"""
import argparse
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--published", action="store_true",
                    help="published config instead of the smoke preset")
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args()

    if args.devices:
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={args.devices}")
    import time

    import jax
    import numpy as np
    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import mesh_from_devices
    from repro.models import transformer
    from repro.serve import Engine, Request, ServeSpec

    enable_compile_cache()
    devs = jax.devices()[:args.devices] if args.devices else jax.devices()
    mesh = mesh_from_devices(devs)
    jax.set_mesh(mesh)

    cfg = configs.get(args.arch) if args.published else configs.get_smoke(
        args.arch)
    if cfg.family == "audio":
        raise SystemExit("use examples/serve_batched.py for the enc-dec path")
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    # round the cache up to page granularity (page_len must divide cache_len)
    need = args.prompt_len + args.max_new
    spec = ServeSpec(batch=args.batch, cache_len=-(-need // 16) * 16)
    eng = Engine(cfg, mesh, params, spec)
    del params                     # the engine holds its own bf16 copy
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (args.batch, args.prompt_len), dtype=np.int32)
    t0 = time.perf_counter()
    for i in range(args.batch):
        eng.submit(Request(tokens=prompts[i], max_new=args.max_new))
    results = eng.drain()
    dt = time.perf_counter() - t0
    sample = results[0].tokens[:12]
    print(f"[serve] {jax.devices()[0].platform}: drained {len(results)} "
          f"requests ({args.batch * args.max_new} tokens) in {dt:.2f}s "
          f"({args.batch * args.max_new / dt:.1f} tok/s); sample: {sample}")


if __name__ == "__main__":
    main()
