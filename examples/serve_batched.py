"""Serve a small model with batched requests: prefill once, decode in a
batch, report per-token latency.

    PYTHONPATH=src python examples/serve_batched.py --arch mamba2-780m
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    args = ap.parse_args()

    os.environ.setdefault(
        "XLA_FLAGS",
        f"--xla_force_host_platform_device_count={args.devices}")
    import time

    import jax
    import numpy as np

    import warnings

    from repro import configs
    from repro.launch.mesh import make_mesh
    from repro.models import transformer
    from repro.serve import Engine, ServeSpec

    mesh = make_mesh((args.devices // 2, 2), ("data", "model"))
    jax.set_mesh(mesh)
    cfg = configs.get_smoke(args.arch)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, mesh, params,
                 ServeSpec(batch=args.batch,
                           cache_len=args.prompt_len + args.max_new))

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len), dtype=np.int32)
    t0 = time.perf_counter()
    # the lockstep wave is exactly what this example measures (whole-batch
    # per-token latency), so it keeps the deprecated generate loop on purpose
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        toks = eng.generate(prompts, max_new=args.max_new)
    dt = time.perf_counter() - t0
    n_tok = args.batch * args.max_new
    print(f"[serve] {cfg.name}: {n_tok} tokens in {dt:.2f}s "
          f"({n_tok/dt:.1f} tok/s, {dt/args.max_new*1e3:.1f} ms/decode-step)")
    print("sample:", toks[0][:16])


if __name__ == "__main__":
    main()
