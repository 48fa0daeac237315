import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production mesh, record memory_analysis / cost_analysis / collective
schedule, and derive the roofline terms.

The two lines above MUST stay the very first statements in this module —
jax locks the device count on first init, and the dry-run (and ONLY the
dry-run) needs 512 placeholder host devices.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/dryrun]
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs.base import shape_applicable
from repro.configs.registry import get_config, get_shape, list_archs, list_shapes
from repro.launch import hlo_analysis as H
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import setup_for


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            momentum_dtype: str = "bfloat16", use_kernels: bool = False,
            seq_parallel: bool = True, ce_chunk: int = 0,
            verbose: bool = True, setup=None) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, reason = shape_applicable(cfg, shape)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind, "applicable": ok,
    }
    if not ok:
        rec["skip_reason"] = reason
        return rec

    n_chips = 512 if multi_pod else 256
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    if setup is None:
        step_fn, args, in_shardings = setup_for(
            cfg, shape, mesh, momentum_dtype=momentum_dtype,
            use_kernels=use_kernels, seq_parallel=seq_parallel,
            ce_chunk=ce_chunk)
    else:
        # custom setup (perf experiments pass their own variant)
        step_fn, args, in_shardings = setup(cfg, shape, mesh)
    # realistic buffer aliasing: train updates params/opt in place, decode
    # updates the cache in place
    donate = {"train": (0, 1), "decode": (1,), "prefill": ()}[shape.kind]
    with jax.set_mesh(mesh):
        lowered = jax.jit(step_fn, in_shardings=in_shardings,
                          donate_argnums=donate).lower(*args)
        compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 1)

    # --- memory ---------------------------------------------------------
    try:
        mem = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "peak_bytes": int(mem.argument_size_in_bytes
                              + mem.temp_size_in_bytes),
        }
        if verbose:
            print(f"  memory_analysis: args={rec['memory']['argument_bytes']/2**30:.2f}GiB "
                  f"temp={rec['memory']['temp_bytes']/2**30:.2f}GiB "
                  f"(per device)")
    except Exception as e:  # CPU backend may not implement it
        rec["memory"] = {"error": str(e)[:200]}

    # --- loop-aware HLO analysis (FLOPs, HBM bytes, collectives) ---------
    # raw cost_analysis is recorded too, but it counts while bodies once —
    # the loop-aware parse is authoritative (see hlo_analysis.py).
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    rec["xla_cost_raw"] = {"flops": float(cost.get("flops", 0.0)),
                           "bytes": float(cost.get("bytes accessed", 0.0))}
    hlo = compiled.as_text()
    stats = H.analyze(hlo)
    dev_flops = stats.flops
    dev_bytes = stats.bytes_hbm
    rec["cost"] = {"device_flops": dev_flops, "device_bytes": dev_bytes}
    # TPU-aliased (in-place DUS) memory model: tighter estimate for decode
    rec["memory_s_dus_aliased"] = (
        H.analyze(hlo, dus_aliased=True).bytes_hbm / H.HBM_BW)
    rec["collectives"] = stats.coll_dict()
    rec["collective_bytes"] = float(stats.collective_bytes)
    rec["n_whiles"] = stats.n_whiles
    rec["trip_counts"] = stats.trip_counts
    rec["hlo_lines"] = hlo.count("\n")

    # --- roofline ---------------------------------------------------------
    terms = H.roofline_terms(dev_flops, dev_bytes, stats.collective_bytes)
    rec["roofline"] = terms
    rec["bottleneck"] = H.dominant_term(terms)
    n_tokens = (shape.global_batch * shape.seq_len
                if shape.kind != "decode" else shape.global_batch)
    mf = H.model_flops(cfg.active_param_count(), n_tokens,
                       train=(shape.kind == "train"))
    rec["model_flops_total"] = mf
    rec["useful_flops_ratio"] = (mf / (dev_flops * n_chips)
                                 if dev_flops else 0.0)
    if verbose:
        print(f"  cost: {dev_flops/1e12:.2f} TFLOP/dev, "
              f"{dev_bytes/2**30:.2f} GiB/dev accessed; "
              f"collectives {stats.collective_bytes/2**30:.3f} GiB/dev")
        print(f"  roofline: compute {terms['compute_s']*1e3:.2f}ms "
              f"memory {terms['memory_s']*1e3:.2f}ms "
              f"collective {terms['collective_s']*1e3:.2f}ms "
              f"-> {rec['bottleneck']}  "
              f"useful/HLO flops {rec['useful_flops_ratio']:.2f}")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--shape", choices=list_shapes())
    ap.add_argument("--all", action="store_true",
                    help="all applicable (arch x shape) combinations")
    ap.add_argument("--multi-pod", action="store_true",
                    help="2x16x16 (512-chip) mesh instead of 16x16")
    ap.add_argument("--momentum-dtype", default="bfloat16",
                    choices=["float32", "bfloat16", "int8"])
    ap.add_argument("--use-kernels", action="store_true")
    ap.add_argument("--no-seq-parallel", action="store_true",
                    help="ablation: disable sequence parallelism")
    ap.add_argument("--ce-chunk", type=int, default=0,
                    help="vocab-chunked CE chunk size (0 = dense)")
    ap.add_argument("--tag", default="",
                    help="suffix for the output record name")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    if args.all:
        combos = [(a, s) for a in list_archs() for s in list_shapes()]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape)]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch, shape in combos:
        tag = f"{arch}_{shape}_{'2x16x16' if args.multi_pod else '16x16'}"
        if args.tag:
            tag += "_" + args.tag
        print(f"[dryrun] {tag}")
        try:
            rec = run_one(arch, shape, multi_pod=args.multi_pod,
                          momentum_dtype=args.momentum_dtype,
                          use_kernels=args.use_kernels,
                          seq_parallel=not args.no_seq_parallel,
                          ce_chunk=args.ce_chunk)
        except Exception as e:
            traceback.print_exc()
            rec = {"arch": arch, "shape": shape, "error": str(e)[:2000]}
            failures.append(tag)
        with open(os.path.join(args.out, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=2)
    if failures:
        print(f"FAILURES ({len(failures)}): {failures}")
        raise SystemExit(1)
    print("dry-run OK")


if __name__ == "__main__":
    main()
