"""Dry-run of the paper's technique on the production mesh.

Lowers Algorithm 1 (the one-shot distributed sparse-LDA estimator) via
shard_map on the 16x16 / 2x16x16 meshes with abstract inputs and
extracts the roofline terms.

Machines = data slices (16 per pod x pods); CLIME columns sharded over
the 16-wide model axis.  ``main()`` forces 512 host devices, so run it
as its own process: ``python -m repro.launch.dryrun_slda``.
"""

import argparse
import json
import os
import re
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.dantzig import DantzigConfig
from repro.core.distributed import distributed_slda_shardmap
from repro.launch import mesh as mesh_lib

# TPU v5e constants (target hardware; container runtime is CPU)
PEAK_FLOPS = 197e12  # bf16 per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(segment: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(segment):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective byte totals from a compiled (post-SPMD) HLO dump.

    Sums the *result* shape bytes of every collective op in the
    per-device module -- i.e. bytes landing on each chip's ICI.
    """
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if "=" not in stripped:
            continue
        lhs, _, rhs = stripped.partition("=")
        op = None
        rhs_head = rhs.strip()
        for c in _COLLECTIVES:
            if rhs_head.startswith(c + "(") or rhs_head.split(" ", 2)[:2][-1:] == [c]:
                op = c
                break
            # result shape precedes op name: "bf16[..] all-gather(...)"
            m = re.match(r"[\w\[\],{}\s/#*()]*?\b" + re.escape(c) + r"\(", rhs_head)
            if m:
                op = c
                break
        if op is None:
            continue
        # shapes appear on the rhs before the op name
        head = rhs_head.split(op + "(")[0]
        nbytes = _shape_bytes(head) or _shape_bytes(lhs)
        out[op] += nbytes
        counts[op] += 1
    return {"bytes": out, "counts": counts, "total_bytes": sum(out.values())}


def _compile_costs(d, n_machines, n1, multi_pod, iters, variant):
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    data_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    # "fused" variant: whole ADMM solve inside the VMEM-resident Pallas
    # kernel (SSPerf-A2); fixed rho, no per-column adaptation.
    cfg = DantzigConfig(max_iters=iters, fused=(variant == "fused"),
                        adapt_rho=(variant != "fused"))
    x_abs = jax.ShapeDtypeStruct((n_machines * n1, d), jnp.float32)
    y_abs = jax.ShapeDtypeStruct((n_machines * n1, d), jnp.float32)
    in_sh = NamedSharding(mesh, P(data_axes, None))

    def fn(x, y):
        return distributed_slda_shardmap(
            mesh, x, y, 0.05, 0.05, 0.01, cfg, data_axes=data_axes,
            model_axis="model",
        )

    with mesh:
        lowered = jax.jit(fn, in_shardings=(in_sh, in_sh),
                          out_shardings=NamedSharding(mesh, P())).lower(x_abs, y_abs)
        compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):  # older JAX returns a 1-elem list
        ca = ca[0] if ca else {}
    coll = collective_bytes(compiled.as_text())
    return (float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0)),
            float(coll["total_bytes"]), coll, compiled)


def run_one(d: int, n_per_machine: int, multi_pod: bool, max_iters: int,
            out_dir: str | None, tag: str = "", variant: str = "baseline"):
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    data_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    n_machines = 1
    for a in data_axes:
        n_machines *= mesh.shape[a]
    n1 = n_per_machine // 2

    t0 = time.time()
    # XLA cost analysis counts the ADMM scan body once; extrapolate the
    # per-iteration delta from 1- vs 2-iteration lowers.
    f1, b1, c1, _, _ = _compile_costs(d, n_machines, n1, multi_pod, 1, variant)
    f2, b2, c2, coll, compiled = _compile_costs(d, n_machines, n1, multi_pod, 2, variant)
    flops = f1 + (max_iters - 1) * (f2 - f1)
    nbytes = b1 + (max_iters - 1) * (b2 - b1)
    cbytes = c1 + (max_iters - 1) * (c2 - c1)
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    print(mem)
    terms = {
        "compute_s": flops / PEAK_FLOPS,
        "memory_s": nbytes / HBM_BW,
        "collective_s": cbytes / ICI_BW,
    }
    dominant = max(terms, key=terms.get)
    # the paper's communication budget: ONE d-vector per machine
    paper_bytes = 4 * d
    result = {
        "arch": "slda-core",
        "variant": variant,
        "d": d,
        "n_per_machine": n_per_machine,
        "machines": n_machines,
        "max_iters": max_iters,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "flops_per_device": flops,
        "bytes_per_device": nbytes,
        "collective_bytes_per_device": cbytes,
        "collectives": coll,
        "paper_uplink_bytes": paper_bytes,
        **terms,
        "dominant": dominant,
        "compile_s": t_compile,
    }
    print(f"[dryrun-slda] d={d} n={n_per_machine} {result['mesh']} {variant}: "
          f"compute={terms['compute_s']:.3e}s memory={terms['memory_s']:.3e}s "
          f"collective={terms['collective_s']:.3e}s dominant={dominant} "
          f"coll_bytes={cbytes:.3e} (compile {t_compile:.0f}s)")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"_{tag}" if tag else ""
        fname = f"slda-core_d{d}_{result['mesh']}_{variant}{suffix}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(result, f, indent=1, default=str)
    return result


def main():
    # must precede JAX's backend start-up, which happens on first use
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--d", type=int, default=256)
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--iters", type=int, default=500)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun_slda")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    for mp in meshes:
        run_one(args.d, args.n, mp, args.iters, args.out, args.tag, args.variant)


if __name__ == "__main__":
    main()
