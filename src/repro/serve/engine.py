"""Continuous-batching serving engine (DESIGN.md §14, §17).

The engine turns the one-shot prefill+decode demo into a request-level
server: an open-loop trace (``serve.trace``) feeds an admission queue, a
bounded pool of decode slots (``serve.cache``) runs **one compiled
decode step over the whole pool per tick**, and slots are evicted the
step their request finishes (EOS or max-tokens) and immediately
backfilled from the queue — prefill interleaves with decode, so a free
slot never waits for the rest of the batch. The contrast baseline,
static rebatching (``mode="static"``), admits a full batch only when the
pool is empty and holds every slot until the whole batch drains — same
hardware, same cost model, same per-request token streams.

Two clocks, deliberately separate:

  * tokens come from the *real* model (``lm_prefill_chunk``/
    ``lm_decode_step`` on the actual params) — a request served from a
    pool slot is token-identical to the same request decoded alone
    (enforced per model family by tests/test_serve_parity.py);
  * *time* is virtual, from a deterministic ``CostModel`` (prefill cost
    affine in prompt length, decode cost affine in pool width), so
    latency distributions, SLO attainment, and scheduler comparisons are
    reproducible on any host and "equal hardware" between policies means
    exactly equal step costs.

Prefill runs in two regimes (§17):

  * **monolithic** (``prefill_chunk=0``): one dispatch consumes the
    whole prompt before anything else happens — the engine loop stalls
    for the full prefill cost, exactly the straggler-blocks-the-barrier
    shape ADSP §3 removes from training. Dispatches are jit-cached by
    the prompt length rounded up to a power of two (padding masked by
    ``n_valid``), so realistic traces compile O(log max_len) prefill
    fns, not one per distinct length.
  * **chunked** (``prefill_chunk=C``, continuous mode): prompts are
    admitted to up to ``prefill_batch`` *lanes* (a second ``CachePool``)
    and advanced C tokens at a time — all lanes in **one dispatch** per
    chunk, ragged rows masked — with the chunk *riding the decode step*
    whenever the pool is busy: one combined step costs
    ``decode(slots) + per_token×chunk`` (``CostModel.piggyback``), so a
    2k-token prompt never stalls the decode pool and pays no per-chunk
    dispatch base. Only a standalone chunk (empty pool) pays a base,
    once per dispatch however many lanes share it — batched prefill
    admission amortizes exactly that.

Admission order is a registered scheduler: ``fcfs`` (arrival order) or
``deadline`` (earliest deadline first — EDF spends slack where it
exists). Between decode steps the engine can poll a ``ReplicaSync``
(``serve.sync``) so the served model tracks a live training PS via
version-stale shard pulls.

The run loop is a stepping API (``submit``/``run_until``) so a
``serve.balance.LoadBalancer`` can drive N engines on one virtual clock;
``run()`` is the single-replica convenience that feeds the engine's own
trace through it.

While the span recorder of ``repro.fleet.metrics`` is on, the engine
also times its host work on the wall clock: one ``serve.step`` span per
action, with ``serve.prefill`` (host prompt build), ``serve.dispatch``
(a jitted call), ``serve.fetch`` (waiting for token ids),
``serve.insert``/``serve.evict`` inside it, and ``serve.admit`` from a
request's ``submit`` to its slot or lane. The virtual clock never reads
them.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.synthetic import lm_tokens
from repro.fleet.metrics import PullRecord, ServeRecord, event, span, stamp
from repro.models import lm

from .cache import CachePool
from .sync import ReplicaSync
from .trace import Request

__all__ = [
    "CostModel", "ServeConfig", "ServeReport", "ServeEngine", "serve_trace",
    "solo_decode",
    "register_scheduler", "get_scheduler", "scheduler_names",
]

Pytree = Any

_EPS = 1e-12


# ---------------------------------------------------------------------------
# virtual step costs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Virtual seconds per engine operation. Affine models: prefill in
    prompt tokens, decode in pool width (every slot is computed whether
    occupied or not — that is precisely static batching's waste).

    Chunked prefill is priced at the *step* level, the way continuous
    batching actually schedules it: when a decode step is running
    anyway, the chunk's tokens ride that step — ``piggyback`` charges
    only their per-token work, the dispatch base is already paid by the
    decode step. Only a *standalone* chunk dispatch (empty decode pool)
    pays a base (``chunk``): the base is paid once per dispatch however
    many lanes advance, which is what batched prefill admission buys."""

    prefill_base: float = 2e-3
    prefill_per_token: float = 2.5e-4
    decode_base: float = 4e-3
    decode_per_slot: float = 1e-3
    chunk_base: float | None = None  # standalone-chunk base (None: prefill_base)

    def prefill(self, prompt_len: int) -> float:
        return self.prefill_base + self.prefill_per_token * prompt_len

    def decode(self, n_slots: int) -> float:
        return self.decode_base + self.decode_per_slot * n_slots

    def chunk(self, tokens: int) -> float:
        base = self.prefill_base if self.chunk_base is None else self.chunk_base
        return base + self.prefill_per_token * tokens

    def piggyback(self, tokens: int) -> float:
        """Marginal cost of chunk tokens sharing a decode step."""
        return self.prefill_per_token * tokens


# ---------------------------------------------------------------------------
# admission schedulers (registry idiom, as repro.ps / repro.transport)
# ---------------------------------------------------------------------------

_SCHEDULERS: dict[str, Callable[[], "AdmissionScheduler"]] = {}


def register_scheduler(name: str):
    def deco(cls):
        _SCHEDULERS[name] = cls
        return cls
    return deco


def get_scheduler(name: str) -> "AdmissionScheduler":
    try:
        return _SCHEDULERS[name]()
    except KeyError:
        raise KeyError(f"unknown scheduler {name!r}; known: {scheduler_names()}")


def scheduler_names() -> list[str]:
    return sorted(_SCHEDULERS)


class AdmissionScheduler:
    """Picks which queued request gets the next free slot."""

    def pick(self, queue: list[Request], t: float) -> int:
        raise NotImplementedError


@register_scheduler("fcfs")
class FCFSScheduler(AdmissionScheduler):
    def pick(self, queue: list[Request], t: float) -> int:
        return min(range(len(queue)),
                   key=lambda i: (queue[i].arrival, queue[i].rid))


@register_scheduler("deadline")
class DeadlineScheduler(AdmissionScheduler):
    """Earliest deadline first (ties to arrival, then rid)."""

    def pick(self, queue: list[Request], t: float) -> int:
        return min(range(len(queue)),
                   key=lambda i: (queue[i].deadline, queue[i].arrival, queue[i].rid))


# ---------------------------------------------------------------------------
# engine config / report
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """slots: decode-slot pool width. mode: 'continuous' (per-step
    evict + backfill) or 'static' (rebatch only when the pool drains).
    sync_every: decode steps between PS polls (0 = never). capacity:
    attention cache length per slot; 0 derives the minimum from the
    trace (max prompt + max new tokens). prefill_chunk: tokens per
    chunked-prefill dispatch (0 = monolithic prefill); prefill_batch:
    concurrent prefill lanes sharing each chunk dispatch."""

    slots: int = 4
    scheduler: str = "fcfs"
    mode: str = "continuous"
    eos_id: int | None = None
    sync_every: int = 0
    capacity: int = 0
    seed: int = 0
    prefill_chunk: int = 0
    prefill_batch: int = 1
    cost: CostModel = dataclasses.field(default_factory=CostModel)

    def __post_init__(self):
        if self.slots < 1:
            raise ValueError("slots must be >= 1")
        if self.mode not in ("continuous", "static"):
            raise ValueError(f"unknown mode {self.mode!r}")
        if self.prefill_chunk < 0:
            raise ValueError("prefill_chunk must be >= 0")
        if self.prefill_batch < 1:
            raise ValueError("prefill_batch must be >= 1")
        if self.prefill_chunk and self.mode != "continuous":
            raise ValueError(
                "chunked prefill interleaves with decode; static mode "
                "rebatches whole pools and cannot use it"
            )


@dataclasses.dataclass
class ServeReport:
    """Everything a run produced: the per-request records (also streamed
    to the metrics sink as they happen) plus aggregates."""

    records: list[ServeRecord]
    t_end: float
    decode_steps: int
    tokens_by_rid: dict[int, list[int]]
    inserts: int
    evictions: int
    sync_polls: int = 0
    sync_pulls: int = 0
    pull_bytes: int = 0
    full_pull_bytes: int = 0  # dense re-pull at the same pull points
    chunk_dispatches: int = 0  # chunked-prefill dispatches (0 = monolithic)

    # ------------------------------------------------------------ derived
    def _vals(self, field: str) -> list[float]:
        return [getattr(r, field) for r in self.records]

    @staticmethod
    def _pct(xs: list[float], q: float) -> float:
        if not xs:
            return 0.0
        xs = sorted(xs)
        return xs[min(len(xs) - 1, int(round(q * (len(xs) - 1))))]

    def percentile(self, field: str, q: float) -> float:
        return self._pct(self._vals(field), q)

    @property
    def total_tokens(self) -> int:
        return sum(r.tokens for r in self.records)

    @property
    def slo_attainment(self) -> float:
        if not self.records:
            return 0.0
        return sum(r.slo_ok for r in self.records) / len(self.records)

    @property
    def goodput(self) -> float:
        """SLO-attained requests per virtual second."""
        if self.t_end <= 0:
            return 0.0
        return sum(r.slo_ok for r in self.records) / self.t_end

    @property
    def tokens_per_s(self) -> float:
        return self.total_tokens / self.t_end if self.t_end > 0 else 0.0


@dataclasses.dataclass
class _Active:
    req: Request
    t_admit: float
    prefill_s: float
    gen: int
    tokens: list[int]


@dataclasses.dataclass
class _Lane:
    """One chunked-prefill lane: a request whose prompt is being consumed
    ``prefill_chunk`` tokens per shared dispatch. ``first`` is the
    prefill argmax once the prompt is fully consumed (the lane then
    waits for a decode slot); ``t_first`` stamps that dispatch."""

    req: Request
    t_admit: float
    consumed: int = 0
    first: int | None = None
    t_first: float = 0.0


def _next_pow2(n: int) -> int:
    return 1 << (n - 1).bit_length() if n > 1 else 1


def _prev_pow2(n: int) -> int:
    return 1 << (n.bit_length() - 1)


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------


class ServeEngine:
    """One serving replica: model + slot pool + admission queue.

    ``sync`` (a ``ReplicaSync``) makes the replica track a live training
    PS; ``tick`` is called as ``tick(engine, t)`` once per decode step
    *before* the sync poll — benchmarks use it to advance a co-running
    trainer to the serving clock and to probe serving-side loss.
    ``replica`` stamps this engine's records when several engines share
    one metrics stream under a ``serve.balance.LoadBalancer``.
    """

    def __init__(self, cfg, params: Pytree, serve_cfg: ServeConfig,
                 trace: list[Request], *, metrics=None,
                 sync: ReplicaSync | None = None,
                 tick: Callable[["ServeEngine", float], None] | None = None,
                 replica: int = 0):
        if cfg.frontend or cfg.encoder is not None:
            raise ValueError(
                "the serve engine drives token-only decoders; "
                f"{cfg.name} needs a modality frontend at prefill"
            )
        if serve_cfg.sync_every and sync is None:
            raise ValueError("sync_every > 0 needs a ReplicaSync")
        self.cfg = cfg
        self.params = params
        self.serve_cfg = serve_cfg
        self.trace = sorted(trace, key=lambda r: (r.arrival, r.rid))
        self.metrics = metrics
        self.sync = sync
        self.tick = tick
        self.replica = replica
        need = max((r.prompt_len + r.max_new for r in self.trace), default=2)
        cap = serve_cfg.capacity or need
        if cap < need:
            raise ValueError(f"capacity {cap} < trace requirement {need}")
        self.pool = CachePool(cfg, serve_cfg.slots, cap)
        self.scheduler = get_scheduler(serve_cfg.scheduler)
        # chunks larger than the smallest ring cache would overwrite keys
        # the chunk's own early queries still need (models.lm.max_chunk_len)
        self._ring_limit = lm.max_chunk_len(cfg, cap)
        if serve_cfg.prefill_chunk and self._ring_limit is not None and \
                serve_cfg.prefill_chunk > self._ring_limit:
            raise ValueError(
                f"prefill_chunk {serve_cfg.prefill_chunk} exceeds the "
                f"smallest ring cache capacity {self._ring_limit} of {cfg.name}"
            )
        self.lanes: CachePool | None = None
        if serve_cfg.prefill_chunk:
            self.lanes = CachePool(cfg, serve_cfg.prefill_batch, cap)
            self._chunk_fn = jax.jit(self._chunk_step)
        self._decode = jax.jit(self._decode_fn)
        # monolithic prefill dispatches, jit-cached by pow2-padded length
        self._prefill_fns: dict[int, Callable] = {}
        self._last_tok = np.zeros((serve_cfg.slots,), np.int32)
        self._slots: dict[int, _Active] = {}
        self._begin()

    # ---------------------------------------------------------- jitted fns
    def _decode_fn(self, params, toks, caches):
        """One pool-wide decode step; the argmax stays on device so the
        loop ships (slots,) token ids, not (slots, vocab) logits."""
        logits, caches = lm.lm_decode_step(self.cfg, params, {"tokens": toks}, caches)
        return jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32), caches

    def _chunk_step(self, params, toks, caches, start, nv):
        """Advance every prefill lane by one (ragged) chunk."""
        logits, caches = lm.lm_prefill_chunk(
            self.cfg, params, {"tokens": toks}, caches, start, n_valid=nv
        )
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), caches

    def _build_prefill_fn(self, padded: int) -> Callable:
        """Monolithic prefill at bucket length ``padded`` (pow2): fresh
        caches + the chunk path over the whole (masked) prompt, split
        into ring-safe sub-blocks when a sliding window caps the chunk."""
        cap = self.pool.capacity
        step = padded if self._ring_limit is None else \
            min(padded, _prev_pow2(self._ring_limit))
        nblk = (padded + step - 1) // step

        def prefill_bucket(params, toks, nv):
            caches = lm.init_decode_caches(self.cfg, 1, cap)
            lgs = []
            for j in range(nblk):
                off = j * step
                lg, caches = lm.lm_prefill_chunk(
                    self.cfg, params, {"tokens": toks[:, off:off + step]},
                    caches, jnp.full((1,), off, jnp.int32),
                    n_valid=jnp.clip(nv - off, 0, step),
                )
                lgs.append(lg)
            # the last *valid* block holds the first-token logits
            jstar = jnp.clip((nv[0] - 1) // step, 0, nblk - 1)
            lg = jnp.stack(lgs)[jstar]  # (1, V)
            return jnp.argmax(lg, axis=-1).astype(jnp.int32), caches

        return jax.jit(prefill_bucket)

    # ------------------------------------------------------------ helpers
    def prompt_tokens(self, req: Request) -> np.ndarray:
        """Deterministic (1, prompt_len) prompt for a request: a pure
        function of (engine seed, rid) — test harnesses rebuild it to
        replay a request solo."""
        toks = lm_tokens(self.serve_cfg.seed, req.rid, 1,
                         req.prompt_len, self.cfg.vocab_size)
        return toks[:, : req.prompt_len]

    def _prefill(self, req: Request):
        padded = _next_pow2(req.prompt_len)
        fn = self._prefill_fns.get(padded)
        if fn is None:
            fn = self._build_prefill_fn(padded)
            self._prefill_fns[padded] = fn
        with span("serve.prefill", req.rid, t=self.t, valid=req.prompt_len,
                  padded=padded):
            toks = np.zeros((1, padded), np.int32)
            toks[:, : req.prompt_len] = self.prompt_tokens(req)
        with span("serve.dispatch", req.rid, t=self.t, program="prefill_bucket",
                  padded=padded):
            tok, caches = fn(self.params, jnp.asarray(toks),
                             jnp.asarray([req.prompt_len], jnp.int32))
        with span("serve.fetch", req.rid, t=self.t):
            return int(tok[0]), caches

    def _version(self) -> int:
        return self.sync.version if self.sync is not None else 0

    def _complete(self, st: _Active, t: float, *, prefill_only: bool = False):
        r = st.req
        t_first = st.t_admit + st.prefill_s
        rec = ServeRecord(
            t=t, req=r.rid,
            queue=st.t_admit - r.arrival,
            prefill=st.prefill_s,
            decode=0.0 if prefill_only else t - t_first,
            total=t - r.arrival,
            tokens=st.gen, slo=r.slo,
            slo_ok=bool(t <= r.deadline + _EPS),
            version=self._version(),
            replica=self.replica,
        )
        self._done.append(rec)
        self._tokens_by_rid[r.rid] = st.tokens
        if self.metrics is not None:
            self.metrics.record(rec)

    # ---------------------------------------------------------- stepping API
    #
    # The balancer drives N engines on one virtual clock through these
    # three calls; run() is the single-replica composition. One _step()
    # performs exactly one *timed* action (a prefill, a chunk dispatch,
    # or a decode step) plus any zero-cost bookkeeping before it, so the
    # clock only ever advances inside _step().

    def _begin(self):
        self.t = 0.0
        self._queue: list[Request] = []
        self._done: list[ServeRecord] = []
        self._tokens_by_rid: dict[int, list[int]] = {}
        self._decode_steps = 0
        self._chunk_dispatches = 0
        self._filling = False  # static mode: batch-formation phase
        self._lanes: dict[int, _Lane] = {}
        self._prompt_np: dict[int, np.ndarray] = {}
        self._chunk_tok = None  # last chunk dispatch's device-side argmaxes
        self._submit_ns: dict[int, int] = {}  # rid -> host stamp, recorder on

    def submit(self, req: Request) -> None:
        """Hand a request to the admission queue (arrival bookkeeping is
        the caller's: submit when the clock reaches ``req.arrival``)."""
        self._queue.append(req)
        t0 = stamp()
        if t0 is not None:
            self._submit_ns[req.rid] = t0

    def _admit(self) -> Request:
        """Take the scheduler's pick off the queue; its ``serve.admit``
        span ends here."""
        req = self._queue.pop(self.scheduler.pick(self._queue, self.t))
        t0 = self._submit_ns.pop(req.rid, None)
        if t0 is not None:
            event("serve.admit", t0, req.rid, t=self.t)
        return req

    @property
    def has_work(self) -> bool:
        return bool(self._queue or self._slots or self._lanes)

    @property
    def n_queued(self) -> int:
        return len(self._queue)

    @property
    def n_active(self) -> int:
        """Requests holding a decode slot or a prefill lane."""
        return len(self._slots) + len(self._lanes)

    def backlog_seconds(self) -> float:
        """Deterministic service-time estimate for everything queued or
        in flight — the ``deadline_slack`` router's load signal."""
        cost = self.serve_cfg.cost
        per_tok = cost.decode(self.serve_cfg.slots)
        s = 0.0
        for st in self._slots.values():
            s += max(st.req.max_new - st.gen, 0) * per_tok
        for lane in self._lanes.values():
            rem = lane.req.prompt_len - lane.consumed
            if rem > 0:
                s += cost.prefill(rem)
            s += lane.req.max_new * per_tok
        for q in self._queue:
            s += cost.prefill(q.prompt_len) + q.max_new * per_tok
        return s

    def run_until(self, t: float) -> None:
        """Process work while the clock is before ``t`` (an action that
        *starts* before ``t`` may finish past it — the caller submits
        arrivals that landed mid-action before the next one). Idle
        engines jump their clock straight to ``t``."""
        while self.has_work and self.t < t - _EPS:
            if not self._step():
                break
        if math.isfinite(t) and not self.has_work and self.t < t:
            self.t = t

    def finish(self) -> ServeReport:
        report = ServeReport(
            records=self._done, t_end=self.t, decode_steps=self._decode_steps,
            tokens_by_rid=self._tokens_by_rid,
            inserts=self.pool.inserts, evictions=self.pool.evictions,
            chunk_dispatches=self._chunk_dispatches,
        )
        if self.sync is not None:
            report.sync_polls = self.sync.polls
            report.sync_pulls = self.sync.pulls
            report.pull_bytes = self.sync.bytes_pulled
            report.full_pull_bytes = self.sync.full_bytes_equiv
        return report

    # -------------------------------------------------------------- steps
    def _step(self) -> bool:
        """One timed action; False when nothing can run (idle)."""
        with span("serve.step", t=self.t, slots=len(self._slots),
                  queued=len(self._queue)) as sp:
            action = (self._step_chunked() if self.serve_cfg.prefill_chunk
                      else self._step_monolithic())
            sp.set(action=action or "idle", has_work=int(self.has_work))
        return action is not None

    def _step_monolithic(self) -> str | None:
        cfg = self.serve_cfg
        if cfg.mode == "static" and not self._slots and self._queue:
            self._filling = True
        can_admit = (self.pool.n_free > 0 and
                     (cfg.mode == "continuous" or self._filling))

        if self._queue and can_admit:
            req = self._admit()
            t_admit = self.t
            first, caches = self._prefill(req)
            pf = cfg.cost.prefill(req.prompt_len)
            self.t += pf
            st = _Active(req=req, t_admit=t_admit, prefill_s=pf,
                         gen=1, tokens=[first])
            done_now = (req.max_new <= 1 or
                        (cfg.eos_id is not None and first == cfg.eos_id))
            if done_now:
                self._complete(st, self.t, prefill_only=True)
            else:
                with span("serve.insert", req.rid, t=self.t) as sp:
                    slot = self.pool.insert(req.rid, caches)
                    sp.set(slot=slot)
                self._last_tok[slot] = first
                self._slots[slot] = st
            return "prefill"
        self._filling = False

        if not self._slots:
            return None
        self._decode_step()
        return "decode"

    def _step_chunked(self) -> str | None:
        # lane admission is zero-cost bookkeeping: the scheduler hands
        # queued requests to free lanes, then finished lanes drain into
        # free decode slots, then exactly one timed step runs. When both
        # kinds of work exist, the chunk rides the decode step (one
        # combined step: decode cost + the chunk's per-token work); a
        # standalone chunk (empty pool) pays its own dispatch base.
        chunk = self.serve_cfg.prefill_chunk
        while self._queue and self.lanes.n_free > 0:
            req = self._admit()
            with span("serve.insert", req.rid, t=self.t, pool="lanes") as sp:
                slot = self.lanes.admit(req.rid)
                sp.set(slot=slot)
            self._lanes[slot] = _Lane(req=req, t_admit=self.t)
            with span("serve.prefill", req.rid, t=self.t, valid=req.prompt_len,
                      padded=-(-req.prompt_len // chunk) * chunk):
                self._prompt_np[req.rid] = self.prompt_tokens(req)
        self._drain_ready()

        chunk_work = any(l.first is None for l in self._lanes.values())
        decode_work = bool(self._slots)
        if chunk_work and decode_work:
            pend = self._chunk_issue()
            self._decode_step(piggyback_tokens=pend[2])
            self._chunk_finalize(pend)
            self._drain_ready()
            return "chunk+decode"
        if chunk_work:
            pend = self._chunk_issue()
            self.t += self.serve_cfg.cost.chunk(pend[2])
            self._chunk_finalize(pend)
            self._drain_ready()
            return "chunk"
        if decode_work:
            self._decode_step()
            return "decode"
        return None

    def _chunk_issue(self):
        """Dispatch one (ragged) chunk over every mid-prompt lane.
        Device work only — the clock and lane bookkeeping advance in
        ``_chunk_finalize`` once the step this dispatch rides is priced.
        Returns (active lane slots, per-lane valid counts, total)."""
        n_lanes, chunk = self.lanes.n_slots, self.serve_cfg.prefill_chunk
        blk = np.zeros((n_lanes, chunk), np.int32)
        nv = np.zeros((n_lanes,), np.int32)
        start = np.zeros((n_lanes,), np.int32)
        active = []
        for slot in sorted(self._lanes):
            lane = self._lanes[slot]
            if lane.first is not None:
                continue  # prefilled, waiting for a decode slot
            n = min(chunk, lane.req.prompt_len - lane.consumed)
            nv[slot], start[slot] = n, lane.consumed
            prompt = self._prompt_np[lane.req.rid]
            blk[slot, :n] = prompt[0, lane.consumed:lane.consumed + n]
            active.append(slot)
        with span("serve.dispatch", t=self.t, program="chunk", tokens=int(nv.sum())):
            tok, self.lanes.caches = self._chunk_fn(
                self.params, jnp.asarray(blk), self.lanes.caches,
                jnp.asarray(start), jnp.asarray(nv),
            )
        self._chunk_dispatches += 1
        self._chunk_tok = tok  # device array; fetched in finalize
        return active, nv, int(nv.sum())

    def _chunk_finalize(self, pend) -> None:
        cfg = self.serve_cfg
        active, nv, _ = pend
        with span("serve.fetch", t=self.t):
            tok_host = np.asarray(self._chunk_tok)
        for slot in active:
            lane = self._lanes[slot]
            lane.consumed += int(nv[slot])
            if lane.consumed >= lane.req.prompt_len:
                lane.first = int(tok_host[slot])
                lane.t_first = self.t
                done_now = (lane.req.max_new <= 1 or
                            (cfg.eos_id is not None and
                             lane.first == cfg.eos_id))
                if done_now:
                    st = _Active(req=lane.req, t_admit=lane.t_admit,
                                 prefill_s=lane.t_first - lane.t_admit,
                                 gen=1, tokens=[lane.first])
                    self._complete(st, self.t, prefill_only=True)
                    self._free_lane(slot)

    def _free_lane(self, slot: int) -> None:
        lane = self._lanes.pop(slot)
        with span("serve.evict", lane.req.rid, t=self.t, pool="lanes", slot=slot):
            self.lanes.evict(lane.req.rid)
        del self._prompt_np[lane.req.rid]

    def _drain_ready(self) -> None:
        """Move prefilled lanes into free decode slots (lane order)."""
        for slot in sorted(self._lanes):
            lane = self._lanes[slot]
            if lane.first is None:
                continue
            if self.pool.n_free == 0:
                break
            with span("serve.insert", lane.req.rid, t=self.t) as sp:
                src = self.lanes.extract(lane.req.rid)
                dslot = self.pool.insert(lane.req.rid, src)
                sp.set(slot=dslot)
            self._last_tok[dslot] = lane.first
            self._slots[dslot] = _Active(
                req=lane.req, t_admit=lane.t_admit,
                prefill_s=lane.t_first - lane.t_admit,
                gen=1, tokens=[lane.first],
            )
            self._free_lane(slot)

    def _decode_step(self, piggyback_tokens: int = 0) -> None:
        cfg = self.serve_cfg
        with span("serve.dispatch", t=self.t, program="decode"):
            toks = jnp.asarray(self._last_tok[:, None])
            tok_ids, self.pool.caches = self._decode(
                self.params, toks, self.pool.caches
            )
        self.t += cfg.cost.decode(cfg.slots)
        if piggyback_tokens:
            self.t += cfg.cost.piggyback(piggyback_tokens)
        self._decode_steps += 1

        if self.tick is not None:
            self.tick(self, self.t)
        if (self.sync is not None and cfg.sync_every
                and self._decode_steps % cfg.sync_every == 0):
            self.params, n_stale, nbytes, secs = self.sync.poll(self.params)
            self.t += secs
            if n_stale and self.metrics is not None:
                self.metrics.record(PullRecord(
                    t=self.t, stale_shards=n_stale,
                    n_shards=self.sync.plan.n_shards, nbytes=float(nbytes),
                    replica=self.replica,
                ))

        with span("serve.fetch", t=self.t):
            next_tok = np.asarray(tok_ids)
        for slot in sorted(self._slots):
            st = self._slots[slot]
            tok = int(next_tok[slot])
            st.tokens.append(tok)
            st.gen += 1
            self._last_tok[slot] = tok
            if (st.gen >= st.req.max_new or
                    (cfg.eos_id is not None and tok == cfg.eos_id)):
                self._complete(st, self.t)
                with span("serve.evict", st.req.rid, t=self.t, slot=slot):
                    self.pool.evict(st.req.rid)
                del self._slots[slot]
        if self.serve_cfg.prefill_chunk:
            self._drain_ready()

    # -------------------------------------------------------------- run
    def run(self) -> ServeReport:
        self._begin()
        for req in self.trace:
            self.run_until(req.arrival)
            self.submit(req)
        self.run_until(math.inf)
        return self.finish()


def serve_trace(cfg, params: Pytree, serve_cfg: ServeConfig,
                trace: list[Request], **kw) -> ServeReport:
    """Convenience: build an engine and run the trace to completion."""
    return ServeEngine(cfg, params, serve_cfg, trace, **kw).run()


def solo_decode(cfg, params: Pytree, prompt: np.ndarray, max_new: int,
                capacity: int, *, eos_id: int | None = None) -> list[int]:
    """Reference decode of one request alone (batch 1) at the same cache
    capacity a pool would give it — the token-identity oracle for
    tests/test_serve_parity.py and the degenerate one-shot path."""
    plen = prompt.shape[1]
    logits, caches = lm.lm_prefill(
        cfg, params, {"tokens": jnp.asarray(prompt, jnp.int32)},
        reserve=capacity - plen,
    )
    tok = int(jnp.argmax(logits[0]))
    out = [tok]
    while len(out) < max_new and not (eos_id is not None and tok == eos_id):
        lg, caches = lm.lm_decode_step(
            cfg, params, {"tokens": jnp.asarray([[tok]], jnp.int32)}, caches
        )
        tok = int(jnp.argmax(lg[0, 0]))
        out.append(tok)
    return out
