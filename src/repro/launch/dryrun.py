import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape ×
mesh) combination on 512 placeholder host devices, and derive the roofline
terms from the compiled artifact.

The two lines above MUST stay first: jax locks the device count on first
init, and only the dry-run wants 512 fake devices (tests/benches see 1).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-8b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
        --out results/dryrun
Writes one JSON per combination with memory/cost/roofline data.
"""

import argparse
import json
import pathlib
import sys
import time
import traceback

import jax

from repro.configs import ARCH_IDS, get_config, get_smoke
from repro.launch import specs as S
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build
from repro.roofline import model_flops, roofline_terms, xla_cost_dict

SHAPE_NAMES = list(S.SHAPES)


def run_one(arch: str, shape: str, mesh_name: str, tau: int = 4,
            attn_impl: str = "scan", overrides: dict | None = None,
            smoke: bool = False) -> dict:
    from repro.configs import canonical

    arch = canonical(arch)
    cfg = get_smoke(arch) if smoke else get_config(arch)
    spec = S.SHAPES[shape]
    ok, note = S.shape_supported(cfg, shape)
    if not ok:
        return {
            "arch": arch, "shape": shape, "mesh": mesh_name,
            "status": "skipped", "reason": note,
        }
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    n_chips = mesh.devices.size
    overrides = dict(overrides or {})
    t0 = time.time()
    with jax.set_mesh(mesh):
        bundle = build(cfg, mesh, shape, tau=tau, attn_impl=attn_impl,
                       **overrides)
        lowered = bundle.lower()
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        cost = xla_cost_dict(compiled)
        try:
            mem = compiled.memory_analysis()
            mem_d = {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
            }
        except Exception as e:  # CPU backend may not implement it
            mem_d = {"error": str(e)}
        hlo = compiled.as_text()

    mf = model_flops(S.effective_config(cfg, shape), spec,
                     tau=tau if spec.kind == "train" else 1)
    rep = roofline_terms(
        arch=arch, shape=shape, mesh_name=mesh_name, n_chips=n_chips,
        cost=cost, hlo_text=hlo, model_flops_total=mf,
    )
    out = {
        "arch": arch, "shape": shape, "mesh": mesh_name, "status": "ok",
        "variant_note": note, "tau": tau,
        "n_chips": n_chips,
        "step": bundle.name,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory_analysis": mem_d,
        "cost_analysis": {k: cost.get(k) for k in ("flops", "bytes accessed")},
        "roofline": rep.to_dict(),
        "hlo_collective_lines": sum(
            1 for l in hlo.splitlines()
            if any(c in l for c in ("all-reduce", "all-gather", "reduce-scatter",
                                    "all-to-all", "collective-permute"))
        ),
    }
    # analytic per-chip parameter bytes (sanity vs memory_analysis)
    ap = S.abstract_params(S.effective_config(cfg, shape))
    psh = S.param_shardings(S.effective_config(cfg, shape), mesh,
                            "accum" if spec.kind != "train" else None)
    tot = 0
    for leaf, sh in zip(jax.tree.leaves(ap), jax.tree.leaves(psh)):
        n_shards = 1
        for dim, axis in zip(leaf.shape, sh.spec + (None,) * 8):
            if axis is not None:
                names = axis if isinstance(axis, tuple) else (axis,)
                for a in names:
                    n_shards *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]
        tot += leaf.size * leaf.dtype.itemsize / n_shards
    out["analytic_param_bytes_per_chip"] = int(tot)
    return out


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch")  # any alias; canonicalized below
    p.add_argument("--shape", choices=SHAPE_NAMES)
    p.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    p.add_argument("--tau", type=int, default=4)
    p.add_argument("--commit-dtype", default="float32")
    p.add_argument("--granularity", default="", help="override adsp granularity (train shapes)")
    p.add_argument("--attn-block", type=int, default=512)
    p.add_argument("--tag", default="", help="suffix for output filenames (perf iterations)")
    p.add_argument("--attn-impl", default="scan")
    p.add_argument("--all", action="store_true", help="run every arch × shape")
    p.add_argument("--out", default="results/dryrun")
    p.add_argument("--smoke", action="store_true", help="reduced configs (fast CI)")
    args = p.parse_args(argv)

    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    archs = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else SHAPE_NAMES

    failures = 0
    for arch in [a.replace("-", "_").replace(".", "_") for a in archs]:
        for shape in shapes:
            for mesh_name in meshes:
                tag = f"{arch}__{shape}__{mesh_name}" + (f"__{args.tag}" if args.tag else "")
                fp = outdir / f"{tag}.json"
                if fp.exists():
                    print(f"[skip existing] {tag}")
                    continue
                print(f"[dryrun] {tag} ...", flush=True)
                t0 = time.time()
                try:
                    over = ({"commit_dtype": args.commit_dtype,
                             "attn_block": args.attn_block}
                            if S.SHAPES[shape].kind == "train" else {})
                    if args.granularity and S.SHAPES[shape].kind == "train":
                        over["granularity"] = args.granularity
                    res = run_one(arch.replace("-", "_"), shape, mesh_name,
                                  tau=args.tau, attn_impl=args.attn_impl,
                                  smoke=args.smoke, overrides=over)
                except Exception as e:
                    traceback.print_exc()
                    res = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "status": "error", "error": f"{type(e).__name__}: {e}"}
                    failures += 1
                res["wall_s"] = round(time.time() - t0, 1)
                fp.write_text(json.dumps(res, indent=2, default=str))
                status = res["status"]
                rl = res.get("roofline", {})
                print(f"  -> {status} ({res['wall_s']}s) "
                      f"bottleneck={rl.get('bottleneck')} "
                      f"flops/chip={rl.get('hlo_flops'):.3g}" if status == "ok"
                      else f"  -> {status}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
