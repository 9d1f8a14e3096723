"""Run one cell of the benchmark once, on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root
of the checkout. With ``--trace 0`` the run measures the cell's
end-to-end metrics; with ``--trace 1`` it traces the window with the
profiler and reports the cell's per-layer metrics, read by
``metrics/<name>.py`` from the reduced trace. Either way it checks what
the timed path produced against the plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``breakdown``), and last ``checks``, each compared number beside
its limit; the last lines of standard error repeat the checks. A
machine without the chips the cell asks for, or whose device kind has
no entry in ``peaks.json``, makes the run exit with code 3 and print no
result; a checkout without the program's sources, with code 2.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _cell_metrics(bench: dict, section: str, cell: dict) -> list:
    """The metrics of ``section`` this cell reports: those that list it,
    and those with no list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])}
    out = []
    for m in bench[section]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


def read_layers(ctx, out: dict):
    """Per-layer metrics, busy seconds, window seconds and breakdown of a
    traced run."""
    from chipbench import harness, tracing

    trace = tracing.load(out["trace_dir"])
    view = harness.LayerView(trace=trace, facts=out["facts"], peak=ctx.peak,
                             chips=ctx.cell["chips"], traffic=ctx.traffic)
    metrics = {}
    for m in _cell_metrics(ctx.bench, "per_layer", ctx.cell):
        reader = harness.load_module(harness.HERE / "metrics" / f"{m['name']}.py")
        value = reader.read(view)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    devs = sorted(trace.ops)[: ctx.cell["chips"]]
    busy = sum(tracing.busy_ns(trace, d) for d in devs) / max(len(devs), 1) / 1e9
    breakdown = {"device_ops": tracing.top_ops(trace),
                 "idle_gaps": tracing.idle_gaps(trace, devs[0]) if devs else []}
    return metrics, busy, trace.window_ns / 1e9, breakdown


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: no program sources under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness

    ctx = harness.load_cell(args.workload, seed=args.seed, seconds=args.seconds,
                            trace=bool(args.trace), t_start=T_START)
    try:
        ctx.peak, ctx.device = harness.require_devices(ctx.cell["chips"])
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    harness.enable_cache()
    out = ctx.driver().run(ctx)
    correct, checks = harness.judge(out["readings"], ctx.limits)
    device = dict(ctx.device, memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": correct, "attempted": out["attempted"], "failed": out["failed"]}
    try:
        if ctx.trace:
            metrics, busy, window, breakdown = read_layers(ctx, out)
            device.update(busy_s=busy, window_s=window)
        else:
            names = {m["name"]: m["unit"] for m in _cell_metrics(ctx.bench, "end_to_end", ctx.cell)}
            metrics = {k: {"value": v, "unit": names[k]}
                       for k, (v, _) in out["metrics"].items() if k in names}
    finally:
        harness.cleanup(out["trace_dir"])
    result.update(metrics=metrics, device=device)
    if ctx.trace:
        result["breakdown"] = breakdown
    result["notes"] = out.get("notes", {})
    result["checks"] = checks
    print(json.dumps(out.get("notes", {})), file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
