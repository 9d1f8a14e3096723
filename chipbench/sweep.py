"""Find a serving mix's knee: offer it at several fixed rates in one
process (one set-up) and report, per rate, the end-to-end metrics, how
late the generator ran, the slope of the admission queue's length over
the window, and how long the drain after the window took. The knee is
the highest rate at which the queue does not grow over the window.

    python3 chipbench/sweep.py --workload <serving cell> --rates 4,6,8 --seconds 20 [--seed 1]

The benchmark's runs never run this; a mix's rate is set once from it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def queue_slope(queue, seconds: float) -> float:
    """Least-squares slope (requests per second) of the queue length over
    the window."""
    pts = [(t, q) for t, q in queue if t <= seconds]
    if len(pts) < 2:
        return 0.0
    t, q = np.asarray(pts, np.float64).T
    return float(np.polyfit(t, q, 1)[0])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True, help="comma-separated requests per second")
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness, traffic_gen

    ctx = harness.load_cell(args.workload, seed=args.seed, seconds=args.seconds, trace=False,
                            t_start=T_START)
    try:
        ctx.peak, ctx.device = harness.require_devices(ctx.cell["chips"])
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    harness.enable_cache()
    d = ctx.driver()
    _, engine = d.build(ctx)
    print(json.dumps({"setup_s": time.perf_counter() - T_START}), flush=True)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(ctx.traffic, rate=rate)
        # request ids of their own: the engine keeps every finished
        # request's tokens by id, and the stamps are read from them
        reqs = [dataclasses.replace(r, rid=r.rid + k * 1_000_000)
                for r in traffic_gen.requests(mix, args.seed, args.seconds)]
        w = d.window(ctx, engine, reqs)
        s = d.summarize(ctx, reqs, w)
        s.update(rate=rate, requests=len(reqs),
                 queue_slope_per_s=queue_slope(w["queue"], args.seconds),
                 queue_at_close=([q for t, q in w["queue"] if t <= args.seconds] or [0])[-1])
        print(json.dumps(s), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
