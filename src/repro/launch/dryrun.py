import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this produces, WITHOUT allocating any real arrays
(ShapeDtypeStruct stand-ins only):

  * proof the distribution config is coherent: ``.lower().compile()`` must
    succeed on the 16×16 single-pod mesh and the 2×16×16 multi-pod mesh;
  * ``compiled.memory_analysis()``  — proves the cell fits HBM;
  * ``compiled.cost_analysis()``    — HLO FLOPs / bytes for §Roofline;
  * an HLO collective scan (core/hlo_analysis.py) — collective bytes and
    the local/non-local split of every collective-permute edge.

Results land in ``results/dryrun/<arch>__<shape>__<mesh>[__tag].json``;
existing files are skipped (idempotent, resumable).

Usage::

    python -m repro.launch.dryrun --arch yi-6b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh single          # 40 cells
    python -m repro.launch.dryrun --all --mesh multi           # 40 cells
"""
import argparse
import json
import time
import traceback

import jax
import numpy as np

from repro import configs
from repro.core.hlo_analysis import Roofline, collective_stats
from repro.core.topology import device_pod_map
from repro.launch.mesh import make_production_mesh
from repro.models import encdec, transformer
from repro.serve import ServeSpec
from repro.serve.engine import cache_shardings, cache_specs, make_serve_fns
from repro.train.sharding import dp_axes, param_specs
from repro.train.step import make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def model_flops(cfg, shape) -> float:
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch          # decode: 1 tok/row


def lower_cell(cfg, shape, mesh, *, grad_sync="locality", fsdp=True,
               seq_shard=False, remat=True, moe_dispatch="auto"):
    """Returns the jax ``Lowered`` for one cell (plus the step artifacts
    for train, so the caller can record the resolved MoE dispatch)."""
    if shape.kind == "train":
        # "auto" lets make_train_step resolve expert-parallel dispatch per
        # cell: the tuning policy picks the algorithm where the config is
        # eligible (MoE arch, E and B divisible by the DP span), and the
        # cell degrades to "none" everywhere else
        art = make_train_step(cfg, mesh, grad_sync=grad_sync, fsdp=fsdp,
                              seq_shard=seq_shard, remat=remat,
                              shape=shape, moe_dispatch=moe_dispatch)
        return art.step_fn.lower(art.abstract_state,
                                 dict(cfg.input_specs(shape))), art
    if shape.kind == "prefill":
        art = make_serve_fns(cfg, mesh, ServeSpec(batch=shape.global_batch,
                                                  cache_len=shape.seq_len))
        return art.prefill_fn.lower(art.abstract_params,
                                    dict(cfg.input_specs(shape))), art
    # decode: cache of seq_len context + one-token step
    art = make_serve_fns(cfg, mesh, ServeSpec(batch=shape.global_batch,
                                              cache_len=shape.seq_len))
    c_specs = cache_specs(cfg, shape.global_batch, shape.seq_len)
    tok = jax.ShapeDtypeStruct((shape.global_batch, 1), np.int32)
    return art.decode_fn.lower(art.abstract_params, c_specs, tok), art


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             grad_sync="locality", fsdp=True, seq_shard=False, remat=True,
             moe_dispatch="auto", tag="", out_dir=RESULTS_DIR,
             force=False) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    fname = f"{arch}__{shape_name}__{mesh_kind}{('__' + tag) if tag else ''}.json"
    path = os.path.join(out_dir, fname)
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    cfg = configs.get(arch)
    shape = configs.SHAPES_BY_NAME[shape_name]
    if shape_name == "long_500k" and not cfg.runs_long_500k:
        res = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
               "status": "skipped", "reason": "full-attention arch"}
        with open(path, "w") as f:
            json.dump(res, f, indent=1)
        return res

    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    n_chips = mesh.devices.size
    t0 = time.time()
    res = {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "tag": tag,
           "grad_sync": grad_sync, "fsdp": fsdp, "seq_shard": seq_shard,
           "n_chips": n_chips}
    try:
        with jax.set_mesh(mesh):
            lowered, art = lower_cell(cfg, shape, mesh, grad_sync=grad_sync,
                                      fsdp=fsdp, seq_shard=seq_shard,
                                      remat=remat, moe_dispatch=moe_dispatch)
            if shape.kind == "train":
                res["moe_dispatch"] = art.moe_dispatch
                res["moe_transport"] = art.moe_transport
            compiled = lowered.compile()
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
        pod_map = device_pod_map(mesh, ("pod",)) if multi else None
        stats = collective_stats(hlo, pod_map)
        from repro.telemetry import comm_report
        rep = comm_report(hlo, mesh,
                          label=f"{arch}/{shape_name}/{mesh_kind}")
        res["comm"] = rep.asdict()
        res["locality_schedule"] = rep.has_locality_schedule
        if (multi and shape.kind == "train" and grad_sync == "locality"
                and not rep.has_locality_schedule):
            # the paper's schedule lowers to pod-crossing collective
            # permutes; a locality-configured train cell compiling to HLO
            # with NONE has silently regressed to flat XLA collectives
            raise AssertionError(
                "locality regression: grad_sync='locality' on a multi-pod "
                "mesh compiled to zero pod-crossing collective-permute "
                "edges (flat XLA collectives took over)")
        mf = model_flops(cfg, shape)
        roof = Roofline(flops=float(cost.get("flops", 0.0)),
                        hbm_bytes=float(cost.get("bytes accessed", 0.0)),
                        collective_bytes=float(stats.total_bytes),
                        n_chips=n_chips, model_flops=mf)
        res.update({
            "status": "ok",
            "compile_s": round(time.time() - t0, 1),
            "memory": {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            },
            "cost": {k: float(v) for k, v in cost.items()
                     if isinstance(v, (int, float))},
            "collectives": {
                "counts": dict(stats.counts),
                "bytes": dict(stats.bytes_),
                "permute_edges_local": stats.permute_edges_local,
                "permute_edges_nonlocal": stats.permute_edges_nonlocal,
                "permute_bytes_nonlocal": stats.permute_bytes_nonlocal,
                "group_msgs_nonlocal": stats.group_msgs_nonlocal,
                "group_bytes_nonlocal": stats.group_bytes_nonlocal,
                # the DCN ground truth (permute edges exact + ring-modeled
                # group collectives) benchmarks/multipod.py gates on
                "nonlocal_msgs": stats.nonlocal_msgs,
                "nonlocal_bytes": stats.nonlocal_bytes,
            },
            "model_flops": mf,
            "roofline": roof.row(),
        })
    except Exception as e:  # record the failure — these are bugs to fix
        res.update({"status": "error", "error": f"{type(e).__name__}: {e}",
                    "trace": traceback.format_exc()[-2000:],
                    "compile_s": round(time.time() - t0, 1)})
    with open(path, "w") as f:
        json.dump(res, f, indent=1)
    return res


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--grad-sync", default="locality")
    ap.add_argument("--moe-dispatch", default="auto",
                    choices=["none", "locality", "xla", "auto"])
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=RESULTS_DIR)
    args = ap.parse_args()

    cells = []
    archs = configs.ARCHS if (args.all or not args.arch) else [args.arch]
    for arch in archs:
        cfg = configs.get(arch)
        shapes = ([args.shape] if args.shape
                  else [s.name for s in cfg.shapes()])
        for s in shapes:
            cells.append((arch, s))

    for arch, s in cells:
        r = run_cell(arch, s, args.mesh, grad_sync=args.grad_sync,
                     fsdp=not args.no_fsdp, seq_shard=args.seq_shard,
                     remat=not args.no_remat, moe_dispatch=args.moe_dispatch,
                     tag=args.tag, out_dir=args.out, force=args.force)
        if r["status"] == "ok":
            roof = r["roofline"]
            print(f"[dryrun] {arch:24s} {s:12s} {args.mesh:6s} OK "
                  f"compile={r['compile_s']:.0f}s "
                  f"dom={roof['dominant']:10s} "
                  f"roofline={roof['roofline_fraction']:.3f} "
                  f"peak={_gb(r['memory']['peak_bytes'])}")
        elif r["status"] == "skipped":
            print(f"[dryrun] {arch:24s} {s:12s} {args.mesh:6s} SKIP "
                  f"({r['reason']})")
        else:
            print(f"[dryrun] {arch:24s} {s:12s} {args.mesh:6s} ERROR "
                  f"{r['error'][:120]}")


def _gb(b):
    return f"{b / 2**30:.2f}GiB" if b else "n/a"


if __name__ == "__main__":
    main()
