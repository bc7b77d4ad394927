"""Multi-pod dry-run driver (deliverable e).

Lowers + compiles every (architecture × input shape) cell on the production
mesh — 16×16 single-pod and 2×16×16 multi-pod — and records, per cell:

* ``compiled.memory_analysis()``  (fits-per-device evidence)
* ``compiled.cost_analysis()``    (per-chip FLOPs / bytes for §Roofline)
* collective wire bytes parsed from the compiled HLO
* the LIFE-distributed analytical forecast (made BEFORE compiling —
  the paper's forecast-vs-measured loop, with XLA as the "measurement")

Artifacts: ``artifacts/dryrun/<mesh>/<arch>__<shape>.json``

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--quick]
"""
import argparse
import json
import os
import time
import traceback

from repro.launch.mesh import ensure_host_device_count, make_production_mesh

# the 512-placeholder-device environment, requested BEFORE jax's first
# device use — existing XLA_FLAGS are preserved and the request is a no-op
# if this process already initialized JAX (the count is locked by then)
ensure_host_device_count(512)

import jax

from repro import configs
from repro.core import hlo as hlo_mod
from repro.core import hardware, distributed
from repro.launch import specs as specs_mod


def run_cell(arch: str, shape: str, *, multi_pod: bool = False,
             out_dir: str = "artifacts/dryrun", verbose: bool = True,
             **cell_kwargs) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    skip = specs_mod.cell_is_skipped(arch, shape)
    record = {"arch": arch, "shape": shape, "mesh": mesh_name,
              "n_devices": 512 if multi_pod else 256}
    if skip:
        record["status"] = "SKIP"
        record["reason"] = skip
        _write(record, out_dir, mesh_name, arch, shape)
        if verbose:
            print(f"[{mesh_name}] {arch} × {shape}: SKIP ({skip})")
        return record

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        with mesh:
            cell = specs_mod.build_cell(arch, shape, mesh, **cell_kwargs)
            # LIFE forecast FIRST (hardware-agnostic, pre-compile)
            record["life_forecast"] = specs_mod.life_prediction(cell)
            jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                             out_shardings=cell.out_shardings,
                             donate_argnums=cell.donate)
            lowered = jitted.lower(*cell.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            cost = compiled.cost_analysis()
            mem = compiled.memory_analysis()
            hlo_text = compiled.as_text()
            # loop-folded per-chip cost (cost_analysis counts while bodies
            # once — see repro.core.hlo.analyze)
            mc = hlo_mod.analyze(hlo_text, record["n_devices"])

            flops = mc.flops
            bytes_ = mc.bytes
            wire = mc.wire_bytes
            terms = distributed.roofline(flops, bytes_, wire,
                                         hardware.TPU_V5E)
            mf = distributed.model_flops(cell.workload.arch, cell.tokens,
                                         training=cell.training)
            n_dev = record["n_devices"]
            record.update({
                "status": "OK",
                "lower_s": round(t_lower, 2),
                "compile_s": round(t_compile, 2),
                "per_chip": {
                    "flops": flops,
                    "bytes": bytes_,
                    "collective_wire_bytes": wire,
                    "collective_wire_by_op": mc.collective_wire,
                    "collective_counts": mc.collective_counts,
                    "unknown_trip_loops": mc.unknown_trip_loops,
                    "xla_cost_analysis_flops_unfolded": float(
                        cost.get("flops", 0.0)),
                    "xla_cost_analysis_bytes_unfolded": float(
                        cost.get("bytes accessed", 0.0)),
                },
                "memory_analysis": _mem_dict(mem),
                "roofline": {
                    "t_compute_s": terms.t_compute,
                    "t_memory_s": terms.t_memory,
                    "t_collective_s": terms.t_collective,
                    "dominant": terms.dominant,
                    "bound_time_s": terms.bound_time,
                },
                "model_flops": mf,
                "model_flops_per_chip": mf / n_dev,
                "useful_flops_ratio": (mf / n_dev) / flops if flops else 0.0,
                "tokens": cell.tokens,
            })
            if verbose:
                r = record["roofline"]
                print(f"[{mesh_name}] {arch} × {shape}: OK "
                      f"compile={t_compile:.1f}s  "
                      f"tc={r['t_compute_s']:.3e} tm={r['t_memory_s']:.3e} "
                      f"tx={r['t_collective_s']:.3e} → {r['dominant']}  "
                      f"useful={record['useful_flops_ratio']:.2f}")
    except Exception as e:  # a failing cell is a bug — surface it loudly
        record["status"] = "FAIL"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[{mesh_name}] {arch} × {shape}: FAIL {record['error']}")
    _write(record, out_dir, mesh_name, arch, shape)
    return record


def _mem_dict(mem) -> dict:
    if mem is None:
        return {}
    keys = ("generated_code_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "alias_size_in_bytes",
            "temp_size_in_bytes")
    return {k: int(getattr(mem, k, 0)) for k in keys}


def _write(record, out_dir, mesh_name, arch, shape):
    d = os.path.join(out_dir, mesh_name)
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, f"{arch}__{shape}.json"), "w") as f:
        json.dump(record, f, indent=1)


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", choices=sorted(configs.ARCHS), default=None)
    p.add_argument("--shape", choices=sorted(configs.SHAPES), default=None)
    p.add_argument("--all", action="store_true",
                   help="run every assigned (arch × shape) cell")
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--both-meshes", action="store_true")
    p.add_argument("--out", default="artifacts/dryrun")
    p.add_argument("--kv-dtype", default="bf16", choices=["bf16", "int8"])
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--no-remat", action="store_true")
    p.add_argument("--remat-policy", default="full",
                   choices=["full", "dots", "dots_no_batch"])
    p.add_argument("--moe-dispatch", default="local",
                   choices=["local", "a2a", "global"])
    args = p.parse_args()

    import jax.numpy as jnp
    from repro.models import blocks as _blocks
    _blocks.MOE_DISPATCH = args.moe_dispatch
    kvd = {"bf16": jnp.bfloat16, "int8": jnp.int8}[args.kv_dtype]
    kw = dict(kv_dtype=kvd, microbatches=args.microbatches,
              remat=not args.no_remat, remat_policy=args.remat_policy,
              out_dir=args.out)

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    cells = []
    if args.all:
        for a in configs.ASSIGNED:
            for s in configs.SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells = [(args.arch, args.shape)]

    n_fail = 0
    for mp in meshes:
        for a, s in cells:
            rec = run_cell(a, s, multi_pod=mp, **kw)
            n_fail += rec["status"] == "FAIL"
    if n_fail:
        raise SystemExit(f"{n_fail} dry-run cells FAILED")


if __name__ == "__main__":
    main()
