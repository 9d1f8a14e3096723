"""Event-trace race validator: replay a metrics stream and check the
orderings the control plane promises (DESIGN.md §15).

The static rules prove the *code* can't read the wall clock or drop a
protocol record; this validator proves a given *run* kept its ordering
contracts. It replays a ``MetricsLog`` (or its JSONL persistence) and
asserts:

  * **clock monotonicity** — record timestamps never go backwards in
    stream order (the simulator's re-entrant ``_run_until`` clock guards
    exist precisely to keep this true across nested probe windows);
  * **exactly-one-WorkerLeft** — a worker's leave/join churn records
    alternate: a second leave without an intervening join means a
    scripted departure raced a lease expiry past the dedupe (the PR 6
    bug class);
  * **no stale-gen deliveries** — no commit/capability/assign record for
    a worker inside its dead window (after leave, before rejoin): a
    record there means an event of an expired life (``w.gen``) was
    delivered anyway;
  * **per-shard version monotonicity** — the ``versions`` vector on
    commit records (the PS shard versions the worker's pull reflected)
    never decreases element-wise: a decrease means a stale shard state
    overwrote a newer one.

``python -m repro.analysis.dynamic trace.jsonl`` exits 1 on violations;
CI runs it over the bench_fleet metrics trace.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import Iterable, Sequence

__all__ = ["Violation", "validate_records", "validate_jsonl", "main"]

# record kinds attributed to one worker's *live* lifetime; lease records
# are exempt (the lease layer legitimately reports on dead workers —
# "expired" precedes the leave, "rejoined" precedes the join), and churn
# records are the lifetime boundaries themselves.
_LIFE_KINDS = ("commit", "capability", "assign")


@dataclasses.dataclass(frozen=True)
class Violation:
    """One ordering-contract breach, anchored to the stream index."""

    check: str  # clock | dedupe | stale-gen | shard-version
    index: int  # position in the record stream
    t: float
    message: str
    worker: int | None = None

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "Violation":
        return cls(**d)

    def render(self) -> str:
        who = f" worker={self.worker}" if self.worker is not None else ""
        return f"record #{self.index} t={self.t:.6g}{who}: [{self.check}] {self.message}"


def validate_records(records: Iterable) -> list[Violation]:
    """Replay typed ``MetricRecord``s (e.g. ``MetricsLog.records`` or
    ``repro.fleet.load_jsonl(path)``) and return every violation."""
    out: list[Violation] = []
    last_t = float("-inf")
    alive: dict[int, bool] = {}  # first sight ⇒ implicitly alive
    last_versions: Sequence[int] | None = None

    for i, rec in enumerate(records):
        kind = getattr(rec, "kind", None)
        if kind == "span":
            # host-clock spans join the stream in bulk as a run ends,
            # stamped with the virtual time they opened at: no ordering
            continue
        t = float(getattr(rec, "t", 0.0))
        if t < last_t:
            out.append(Violation(
                check="clock", index=i, t=t,
                message=f"timestamp went backwards: {t:.6g} after {last_t:.6g}"))
        else:
            last_t = t

        wid = getattr(rec, "worker", None)
        if kind == "churn":
            if rec.event == "leave":
                if not alive.get(wid, True):
                    out.append(Violation(
                        check="dedupe", index=i, t=t, worker=wid,
                        message="second WorkerLeft without an intervening "
                                "join (scripted leave raced lease expiry "
                                "past the dedupe)"))
                alive[wid] = False
            elif rec.event == "join":
                if alive.get(wid) is True:
                    out.append(Violation(
                        check="dedupe", index=i, t=t, worker=wid,
                        message="join for an already-alive worker"))
                alive[wid] = True
        elif kind in _LIFE_KINDS and wid is not None:
            if alive.get(wid) is False:
                out.append(Violation(
                    check="stale-gen", index=i, t=t, worker=wid,
                    message=f"{kind} record delivered inside the worker's "
                            "dead window (after leave, before rejoin) — an "
                            "expired-generation event got through"))

        versions = tuple(getattr(rec, "versions", ()) or ())
        if kind == "commit" and versions:
            n_shards = int(getattr(rec, "n_shards", len(versions)))
            if len(versions) != n_shards:
                out.append(Violation(
                    check="shard-version", index=i, t=t, worker=wid,
                    message=f"versions vector has {len(versions)} entries "
                            f"but n_shards={n_shards}"))
            elif last_versions is not None and len(last_versions) == len(versions):
                for k, (prev, cur) in enumerate(zip(last_versions, versions)):
                    if cur < prev:
                        out.append(Violation(
                            check="shard-version", index=i, t=t, worker=wid,
                            message=f"shard {k} version went backwards: "
                                    f"{cur} after {prev} — a stale shard "
                                    "state overwrote a newer one"))
            if last_versions is None or len(last_versions) == len(versions):
                last_versions = tuple(
                    max(p, c) for p, c in zip(last_versions, versions)
                ) if last_versions is not None else versions
    return out


def validate_jsonl(path) -> list[Violation]:
    """Validate a persisted ``MetricsLog.to_jsonl``/``JsonlSink`` file.

    Lines are decoded through the typed registry (``fleet.from_dict``)
    so unknown kinds fail loudly rather than being skipped."""
    from repro.fleet.metrics import load_jsonl

    return validate_records(load_jsonl(path))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m repro.analysis.dynamic",
        description="event-trace race validator over a metrics JSONL")
    p.add_argument("traces", nargs="+", help="metrics JSONL file(s)")
    p.add_argument("--json", metavar="PATH", default=None,
                   help="write violations as JSON ('-' for stdout)")
    args = p.parse_args(argv)

    failed = 0
    all_violations: dict[str, list[dict]] = {}
    for path in args.traces:
        violations = validate_jsonl(path)
        all_violations[path] = [v.to_dict() for v in violations]
        for v in violations:
            print(f"{path}: {v.render()}")
        if violations:
            failed += 1
        else:
            print(f"{path}: OK (no ordering violations)")
    if args.json:
        payload = json.dumps(all_violations, indent=1)
        if args.json == "-":
            print(payload)
        else:
            import pathlib

            pathlib.Path(args.json).write_text(payload)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
