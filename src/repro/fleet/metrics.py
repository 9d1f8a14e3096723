"""Structured metrics stream (DESIGN.md §13).

Every fleet-visible occurrence — a commit round trip, a search, a drift
trigger, a lease grant/expiry, a churn event — is a typed, append-only
record emitted into a shared sink. Producers are the ``ClusterEngine``
(search/drift/churn), the edge simulator (commit latency, push/pull
bytes, shard staleness, lease events), and the mesh backend (per-round
commit records); consumers are ``benchmarks/`` and
``tools/fleet_report.py``.

Records follow the repo's registry idiom (``repro.ps`` rules,
``repro.transport`` codecs): each record class registers under a string
``kind`` and round-trips losslessly through ``to_dict``/``from_dict``,
so a run's stream can be persisted as JSONL and re-loaded for analysis.
Sinks are anything with ``record(rec)``; ``MetricsLog`` keeps the stream
in memory, ``JsonlSink`` appends to a file as the run executes. A ``None``
sink everywhere means "don't record" — producers guard every emission so
an uninstrumented run pays nothing.

Spans (``SpanRecord``) are the one record kind on the host's wall clock:
``span``/``event`` time host work where it happens (the serving engine's
steps, the ADSP round loop) into a bounded process-wide log,
``recorded_spans()``. The recorder is on only while a profiler session
collects or a launcher's stream is open (``span_stream``); off, a span
site costs one check. Each span also opens a profiler
``TraceAnnotation`` of its name, so a trace shows it beside the device's
operations, and compiles that run inside a span become ``compile``
spans under it (from ``jax.monitoring``).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import json
import pathlib
import time
from typing import Iterable, Protocol, runtime_checkable

import jax
from jax.profiler import TraceAnnotation

__all__ = [
    "MetricRecord", "CommitRecord", "EvalRecord", "SearchRecord",
    "DriftRecord", "LeaseRecord", "ChurnRecord", "CapabilityRecord",
    "AssignRecord", "ServeRecord", "PullRecord", "SpanRecord",
    "MetricsSink", "MetricsLog", "JsonlSink",
    "record_kinds", "to_dict", "from_dict", "load_jsonl",
    "recording", "span", "event", "stamp", "span_stream",
    "recorded_spans", "clear_spans",
]


@dataclasses.dataclass(frozen=True)
class MetricRecord:
    """Base class; all records are immutable and carry the (virtual) time
    ``t`` they describe. ``kind`` is the registry key (class attribute)."""

    t: float

    kind = "base"


_KINDS: dict[str, type] = {}


def _register(kind: str):
    def deco(cls):
        cls.kind = kind
        _KINDS[kind] = cls
        return cls
    return deco


def record_kinds() -> list[str]:
    return sorted(_KINDS)


@_register("commit")
@dataclasses.dataclass(frozen=True)
class CommitRecord(MetricRecord):
    """One complete commit round trip (push → apply → pull), stamped at
    pull completion. ``latency`` spans commit decision to pull done —
    barrier waits included, which is what makes it worth recording."""

    worker: int
    latency: float
    push_bytes: float
    pull_bytes: float
    stale_shards: int  # shards the pull actually fetched
    n_shards: int
    # per-shard PS commit counters the pull reflected, in shard order
    # (len n_shards; empty for producers that don't track versions).
    # Element-wise monotone in stream order — the race validator
    # (repro.analysis.dynamic) checks exactly that.
    versions: tuple = ()

    def __post_init__(self):
        if not isinstance(self.versions, tuple):
            object.__setattr__(self, "versions", tuple(self.versions))


@_register("eval")
@dataclasses.dataclass(frozen=True)
class EvalRecord(MetricRecord):
    """A global-loss evaluation (simulator eval clock / mesh round)."""

    loss: float


@_register("search")
@dataclasses.dataclass(frozen=True)
class SearchRecord(MetricRecord):
    """An Alg. 1 SearchSession finished (t = completion time)."""

    chosen: int
    windows: int
    restarts: int
    aborted: bool


@_register("drift")
@dataclasses.dataclass(frozen=True)
class DriftRecord(MetricRecord):
    """A mid-epoch re-search was triggered outside the epoch clock;
    ``cause`` names the event type that carried the Search command."""

    cause: str


@_register("lease")
@dataclasses.dataclass(frozen=True)
class LeaseRecord(MetricRecord):
    """Lease lifecycle: granted | stalled | expired | rejoined."""

    worker: int
    event: str


@_register("churn")
@dataclasses.dataclass(frozen=True)
class ChurnRecord(MetricRecord):
    """Fleet membership changed. ``discovered`` distinguishes failures
    found by the lease layer from scripted/administrative changes."""

    worker: int
    event: str  # "join" | "leave"
    discovered: bool


@_register("capability")
@dataclasses.dataclass(frozen=True)
class CapabilityRecord(MetricRecord):
    """A worker's heartbeat-reported capability (speed v) reached the PS."""

    worker: int
    v: float


@_register("assign")
@dataclasses.dataclass(frozen=True)
class AssignRecord(MetricRecord):
    """The device scheduler (re)assigned a worker's batch/data share."""

    worker: int
    fraction: float
    data_share: float


@_register("serve")
@dataclasses.dataclass(frozen=True)
class ServeRecord(MetricRecord):
    """One inference request completed (``repro.serve`` engine), stamped
    at completion. Latencies decompose the request's life:
    queue (arrival → slot admission) + prefill + decode = total.
    ``version`` is the replica's model version at completion (total shard
    commits reflected; 0 when not tracking training). ``replica`` is the
    serving replica that handled the request (0 for a single engine);
    the default keeps pre-balancer JSONL streams loadable."""

    req: int
    queue: float
    prefill: float
    decode: float
    total: float
    tokens: int
    slo: float
    slo_ok: bool
    version: int
    replica: int = 0


@_register("pull")
@dataclasses.dataclass(frozen=True)
class PullRecord(MetricRecord):
    """A serving replica pulled version-stale shards from the training PS
    between decode steps (``repro.serve.sync``). ``replica`` keeps the
    per-replica pull-bytes story separable under a load balancer."""

    stale_shards: int
    n_shards: int
    nbytes: float
    replica: int = 0


@_register("span")
@dataclasses.dataclass(frozen=True)
class SpanRecord(MetricRecord):
    """A stretch of host work on the wall clock: ``start_ns``/``end_ns``
    from ``time.perf_counter_ns``. ``t`` stays the producer's virtual
    time when the span opened, as on every other record. ``id`` is the
    span's own, ``parent`` the id of the span open around it (None at
    the top); spans of one request or round share ``key``; ``counts``
    holds host-side values at hand (slots, tokens, the program)."""

    name: str
    start_ns: int
    end_ns: int
    id: int
    parent: int | None = None
    key: int | None = None
    counts: dict = dataclasses.field(default_factory=dict)

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns


# ---------------------------------------------------------------------------
# Serialization
# ---------------------------------------------------------------------------


def to_dict(rec: MetricRecord) -> dict:
    d = dataclasses.asdict(rec)
    d = {k: list(v) if isinstance(v, tuple) else v for k, v in d.items()}
    d["kind"] = rec.kind
    return d


def from_dict(d: dict) -> MetricRecord:
    d = dict(d)
    kind = d.pop("kind")
    try:
        cls = _KINDS[kind]
    except KeyError:
        raise KeyError(f"unknown metric kind {kind!r}; known: {record_kinds()}")
    return cls(**d)


def load_jsonl(path) -> list[MetricRecord]:
    out = []
    for line in pathlib.Path(path).read_text().splitlines():
        line = line.strip()
        if line:
            out.append(from_dict(json.loads(line)))
    return out


# ---------------------------------------------------------------------------
# Sinks
# ---------------------------------------------------------------------------


@runtime_checkable
class MetricsSink(Protocol):
    def record(self, rec: MetricRecord) -> None: ...


class MetricsLog:
    """In-memory append-only sink with query helpers."""

    def __init__(self):
        self.records: list[MetricRecord] = []

    def record(self, rec: MetricRecord) -> None:
        self.records.append(rec)

    def of(self, kind: str) -> list[MetricRecord]:
        return [r for r in self.records if r.kind == kind]

    def __len__(self) -> int:
        return len(self.records)

    def to_jsonl(self, path) -> None:
        pathlib.Path(path).write_text(
            "".join(json.dumps(to_dict(r)) + "\n" for r in self.records)
        )

    @classmethod
    def from_records(cls, records: Iterable[MetricRecord]) -> "MetricsLog":
        log = cls()
        for r in records:
            log.record(r)
        return log


class JsonlSink:
    """Streaming JSONL sink: one record per line, flushed as emitted so a
    crashed run still leaves an analyzable prefix."""

    def __init__(self, path):
        self.path = pathlib.Path(path)
        self._fh = self.path.open("w")

    def record(self, rec: MetricRecord) -> None:
        self._fh.write(json.dumps(to_dict(rec)) + "\n")
        self._fh.flush()

    def close(self) -> None:
        self._fh.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ---------------------------------------------------------------------------
# Span recorder
# ---------------------------------------------------------------------------
#
# Process-wide on purpose: the spans of one process share one clock and
# one parent chain however many engines or backends record them. Wall
# clock readings happen here alone; nothing on a virtual clock reads
# them back, so replay stays deterministic.

SPAN_LOG_MAX = 1 << 18  # spans kept; the oldest go first
_span_log: collections.deque = collections.deque(maxlen=SPAN_LOG_MAX)
_open: list = []  # the spans open now, innermost last
_last_id = 0
_streams = 0  # span_stream blocks open
_listening = False

# jax.monitoring's compile-duration events, by compile stage
_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}


_profiling = TraceAnnotation.is_enabled


def recording() -> bool:
    """Whether span sites record: while a profiler session collects, or
    while a ``span_stream`` is open."""
    return _streams > 0 or _profiling()


def _now_ns() -> int:
    """The host clock spans are stamped on (observation only: the
    virtual clocks never read it)."""
    return time.perf_counter_ns()  # reprolint: ignore[wall-clock-in-sim]


def _new_id() -> int:
    global _last_id
    _last_id += 1
    return _last_id


def _listen_compiles() -> None:
    global _listening
    if not _listening:
        _listening = True
        jax.monitoring.register_event_duration_secs_listener(_on_compile)


def _on_compile(event: str, duration: float, **kw) -> None:
    stage = _COMPILE_STAGES.get(event)
    if stage is None or not recording():
        return
    end = _now_ns()
    up = _open[-1] if _open else None
    _span_log.append(SpanRecord(
        t=up.t if up else 0.0, name="compile", start_ns=end - int(duration * 1e9),
        end_ns=end, id=_new_id(), parent=up.id if up else None,
        counts={"stage": stage, "fun": str(kw.get("fun_name", ""))}))


class _Span:
    __slots__ = ("name", "key", "t", "counts", "id", "parent", "start_ns", "_ann")

    def __init__(self, name: str, key, t: float, counts: dict):
        self.name, self.key, self.t, self.counts = name, key, t, counts

    def __enter__(self) -> "_Span":
        self.parent = _open[-1].id if _open else None
        self.id = _new_id()
        _open.append(self)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start_ns = _now_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = _now_ns()
        self._ann.__exit__(*exc)
        _open.pop()
        _span_log.append(SpanRecord(
            t=self.t, name=self.name, start_ns=self.start_ns, end_ns=end,
            id=self.id, parent=self.parent, key=self.key, counts=self.counts))

    def set(self, **counts) -> None:
        """Add counts known only inside the span."""
        self.counts.update(counts)


class _Off:
    """What a span site gets with the recorder off."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        pass

    def set(self, **counts) -> None:
        pass


_OFF = _Off()


def span(name: str, key: int | None = None, *, t: float = 0.0, **counts):
    """Context manager timing the block as a span ``name`` (with the
    producer's virtual time ``t``); inside it, ``.set(**counts)`` adds
    counts. With the recorder off it records nothing."""
    if not (_streams or _profiling()):  # recording(), inlined: the off path
        return _OFF
    _listen_compiles()
    return _Span(name, key, t, counts)


def stamp() -> int | None:
    """The host clock in ns for a span that ``event`` closes later, or
    None with the recorder off."""
    return _now_ns() if recording() else None


def event(name: str, start_ns: int, key: int | None = None, *, t: float = 0.0,
          **counts) -> None:
    """Record a span from ``start_ns`` (a ``stamp``) to now, under the span
    open now. Its start is past, so it has no profiler twin."""
    if not recording():
        return
    up = _open[-1].id if _open else None
    _span_log.append(SpanRecord(
        t=t, name=name, start_ns=start_ns,
        end_ns=_now_ns(), id=_new_id(), parent=up, key=key, counts=counts))


def recorded_spans() -> list[SpanRecord]:
    """The spans recorded so far, in the order they ended."""
    return list(_span_log)


def clear_spans() -> None:
    _span_log.clear()


@contextlib.contextmanager
def span_stream(sink: MetricsSink | None):
    """Keep the recorder on while a launcher's metrics stream is open, and
    append the spans recorded meanwhile to ``sink`` as the block ends.
    ``sink`` None leaves the recorder as it was."""
    global _streams
    if sink is None:
        yield
        return
    _listen_compiles()
    first = _last_id
    _streams += 1
    try:
        yield
    finally:
        _streams -= 1
        for rec in list(_span_log):
            if rec.id > first:
                sink.record(rec)
