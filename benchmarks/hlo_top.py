"""Top-N HBM-traffic instructions of a compiled (arch x shape) program —
the dry-run's stand-in for a profiler. Reuses the loop-aware multiplicities.

Run: PYTHONPATH=src python -m benchmarks.hlo_top --arch gemma3-27b \
        --shape long_500k [--multi-pod] [-n 20]
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse

import jax

from repro.configs.registry import get_config, get_shape, list_archs, list_shapes
from repro.launch import hlo_analysis as H
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import setup_for


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), required=True)
    ap.add_argument("--shape", choices=list_shapes(), required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--use-kernels", action="store_true")
    ap.add_argument("--ce-chunk", type=int, default=0)
    ap.add_argument("-n", type=int, default=20)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    shape = get_shape(args.shape)
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    step_fn, sargs, insh = setup_for(cfg, shape, mesh,
                                     use_kernels=args.use_kernels,
                                     ce_chunk=args.ce_chunk)
    donate = {"train": (0, 1), "decode": (1,), "prefill": ()}[shape.kind]
    with jax.set_mesh(mesh):
        compiled = jax.jit(step_fn, in_shardings=insh,
                           donate_argnums=donate).lower(*sargs).compile()
    text = compiled.as_text()
    comps, entry = H.parse_hlo(text)
    mult = H._multiplicities(comps, entry)

    rows = []
    fusion_bodies = set()
    executed = set([entry])
    for comp in comps.values():
        for ins in comp.instrs:
            if ins.opcode == "fusion":
                t = ins.attr("calls")
                if t:
                    fusion_bodies.add(t)
            if ins.opcode == "while":
                for key in ("body", "condition"):
                    t = ins.attr(key)
                    if t:
                        executed.add(t)
    for cname, comp in comps.items():
        if cname not in executed or cname in fusion_bodies:
            continue
        m = mult.get(cname, 0.0)
        if m <= 0:
            continue
        for ins in comp.instrs:
            if ins.opcode in ("parameter", "constant", "tuple",
                              "get-tuple-element", "bitcast", "while",
                              "conditional"):
                continue
            rb = comp.sizes.get(ins.name, 0)
            ob = sum(comp.sizes.get(nm, 0) for nm in ins.operand_names())
            tot = m * (rb + ob)
            if tot > 0:
                meta = ""
                i = ins.rest.find('op_name="')
                if i >= 0:
                    meta = ins.rest[i + 9:ins.rest.find('"', i + 9)][-70:]
                rows.append((tot, m, ins.opcode, ins.name[:40], meta))
    rows.sort(reverse=True)
    total = sum(r[0] for r in rows)
    print(f"total modeled HBM traffic: {total/2**30:.1f} GiB/device")
    print(f"{'GiB':>9s} {'%':>5s} {'mult':>6s} {'opcode':<22s} op_name")
    for tot, m, op, name, meta in rows[: args.n]:
        print(f"{tot/2**30:9.2f} {100*tot/total:5.1f} {m:6.0f} {op:<22s} "
              f"{meta}")


if __name__ == "__main__":
    main()
