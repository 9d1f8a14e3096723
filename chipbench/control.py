"""The control and the planted faults of a cell's correctness check, at
the cell's own size, several seeds in one process, each judged by
``harness.judge`` against the committed limits of ``cells/<cell>.json``.
The benchmark's own runs never run this; it gives the readings from
which each limit is set, and shows that the control and every fault
come out not correct.

    python3 chipbench/control.py --workload <cell> --seeds 11,12,13 [--more-seeds 14,15] [--seconds 20]

Training cells, per seed: the program's checked rounds (its readings are
lower readings), then the plain reference in the program's place,
computed in fp8 (the control), and with half of the batch left out (a
planted fault), each compared with the float32 reference as a run
compares the program; and the state left unchanged (the program's first
loss, no update), which needs no run. ``--more-seeds`` gives the
program's readings alone.

Serving cells, per seed: one run of the cell at its own load for
``--seconds`` (its reading is a lower reading), then at each served
position of the same sample the gap of the token the fp8 reference
ranks first (the control), and of the served token altered to the next
id at one position of each request (a token altered where produced).

Prints one JSON line per seed, each variant with its readings and
whether it is correct; the last line gathers the largest reading of the
program, the smallest of each control and fault, and whether every
control and fault came out not correct on every seed.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def training(ctx, controls: bool = True) -> dict:
    d = ctx.driver()
    st = d.checked_setup(ctx)
    d.free_program(st)
    P0, taus = st["P0"], st["taus"]
    batches = d.checked_batches(ctx, st)
    ref = d.reference_side(ctx, P0, batches, taus)
    prog = d.program_side(st)
    sides = {"program": prog}
    if controls:
        sides["control_fp8"] = d.reference_side(ctx, P0, batches, taus, mode="fp8")
        sides["half_batch"] = d.reference_side(ctx, P0, batches, taus, fault="half_batch")
        zero = [0.0] * len(prog["update1"])
        sides["unchanged_state"] = {"loss": prog["loss"], "update1": zero, "change3": zero}
    # the first loss's gap is reported beside the compared readings
    return {k: dict(d.compare(v, ref), loss1_gap=d.loss1_gap(v, ref)) for k, v in sides.items()}


def serving(ctx, controls: bool = True) -> dict:
    d = ctx.driver()
    res = d.run(ctx, keep=True)
    kept = res["kept"]
    out = {"program": res["readings"]}
    if controls:
        args = (ctx, kept["params"], kept["samples"], kept["served"])
        altered = {rid: [t if i != len(toks) // 2 else (t + 1) % ctx.model["vocab_size"]
                         for i, t in enumerate(toks)] for rid, toks in kept["served"].items()}
        out["control_fp8"] = {"token_gap": d.reference_gaps(*args, mode="fp8", control=True)}
        out["altered_token"] = {"token_gap": d.reference_gaps(
            ctx, kept["params"], kept["samples"], altered)}
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated seeds")
    p.add_argument("--more-seeds", default="", help="seeds of the program's readings alone")
    p.add_argument("--seconds", type=float, default=20.0)
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness

    seeds = [(int(x), True) for x in args.seeds.split(",")]
    seeds += [(int(x), False) for x in args.more_seeds.split(",") if x]
    worst: dict = {}
    all_caught = True
    for seed, controls in seeds:
        ctx = harness.load_cell(args.workload, seed=seed, seconds=args.seconds, trace=False,
                                t_start=time.perf_counter())
        try:
            ctx.peak, ctx.device = harness.require_devices(ctx.cell["chips"])
        except harness.NoChip as e:
            print(f"chipbench: {e}", file=sys.stderr)
            return 3
        harness.enable_cache()
        t0 = time.perf_counter()
        run = training if ctx.traffic["driver"] == "adsp_train" else serving
        variants = run(ctx, controls)
        line = {"seed": seed, "seconds": time.perf_counter() - t0}
        for variant, readings in variants.items():
            reported = getattr(ctx.driver(), "REPORTED", ())
            correct, _ = harness.judge({k: v for k, v in readings.items() if k not in reported},
                                       ctx.limits)
            line[variant] = {"readings": readings, "correct": correct}
            if variant != "program":
                all_caught &= not correct
            for k, v in readings.items():
                key = f"{variant}.{k}"
                pick = max if variant == "program" else min
                worst[key] = v if key not in worst else pick(worst[key], v)
        print(json.dumps(line), flush=True)
    print(json.dumps({"limits": ctx.limits, "program_max_and_others_min": worst,
                      "every_control_and_fault_not_correct": all_caught}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
