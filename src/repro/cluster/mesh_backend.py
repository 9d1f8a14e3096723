"""Real-hardware backend of the ClusterEngine: the ADSP commit step on a
JAX mesh (DESIGN.md §3–§4).

One *commit round* = every worker runs its τ_i local microsteps (fused,
no cross-worker collective — the no-waiting property) and then all commit
at once via the ``repro.ps.make_train_step`` all-reduce. The update rules
are pluggable (``rules=UpdateRules(...)``): any registered LocalRule
(sgd / sgd_momentum / adamw) at the worker, any CommitRule
(momentum_delta / plain_average) at the PS, reference or Pallas-fused
backend. Heterogeneity is realized through the τ_i vector: the engine's
SetRate commands carry ΔC_i from the policy's rate rule, and the backend
converts them to local step counts τ_i = v_i·(Γ/ΔC_i − O_i), bounded to
[1, cfg.tau] (the compiled step bound).

Clock: ``now`` advances ``round_seconds`` per commit round, so the same
policy object (same Γ, same probe windows) drives this backend and the
virtual-clock simulator. Checkpoint/epoch cadence is driven by
``train(..., check_period=, epoch_rounds=)``.

Spans (``repro.fleet.metrics``, while its recorder is on): one
``adsp.round`` per round (key: the round index; count: Σ τ_i) holding
``adsp.data`` (the microbatches), ``adsp.dispatch`` (the step's call),
``adsp.sync`` (the loss fetch that ends the round on the host) and
``adsp.control`` (τ_i and the engine's commit handling); ``train``'s
checkpoint and epoch calls are ``adsp.control`` too.

Churn: mid-run SpeedChanged is fully supported (speeds only shape τ_i).
WorkerJoined/WorkerLeft are rejected — the worker set is baked into the
compiled SPMD program; elastic membership needs a recompile, which the
virtual-clock backend models instead.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.control import theory
from repro.control.theory import WorkerProfile
from repro.fleet import CommitRecord, EvalRecord, FleetConfig, FleetMonitor
from repro.fleet.metrics import span
from repro.ps import (
    AdspState,
    CommitConfig,
    ShardPlan,
    UpdateRules,
    make_local_update,
    make_train_step,
)
from repro.transport import Codec, dense_nbytes, get_codec

from .engine import ClusterEngine
from .protocol import WorkerView

__all__ = ["MeshTask", "MeshBackend"]

Pytree = object


@dataclasses.dataclass
class MeshTask:
    """The learning problem for the mesh backend, as pure callables.

    loss_fn(params, microbatch) -> scalar loss
    make_microbatches(round_idx, tau, n_workers) -> pytree whose arrays
        have leading dims (tau, global_batch, ...); the batch dim is
        sharded over the worker axes by the compiled step.
    """

    init_params: Pytree
    loss_fn: Callable
    make_microbatches: Callable
    name: str = "mesh_task"


class MeshBackend:
    """See module docstring. Drive with ``train()`` (or ``run_round``)
    after wrapping in a ClusterEngine — the backend dispatches
    ClusterStarted itself on the first round, so do not call
    ``engine.start()`` directly::

        backend = MeshBackend(task, mesh, tau=4)
        engine = ClusterEngine(policy, backend)
        backend.train(rounds=50, check_period=policy.gamma)
    """

    def __init__(
        self,
        task: MeshTask,
        mesh: jax.sharding.Mesh,
        *,
        worker_axes: tuple[str, ...] = ("data",),
        tau: int = 4,
        local_lr: float = 0.05,
        global_lr: float = 1.0,
        commit_dtype: str = "float32",
        profiles: Sequence[WorkerProfile] | None = None,
        round_seconds: float = 1.0,
        batch_spec: P | None = None,
        rules: UpdateRules | None = None,
        explicit_momentum: float = 0.0,
        codec: str | Codec | None = None,
        n_shards: int = 1,
        fused_commit: bool = False,
        overlap_shards: bool = False,
        fleet: FleetConfig | None = None,
        metrics=None,
    ):
        self.task = task
        self.mesh = mesh
        self.tau = tau
        self.round_seconds = round_seconds
        # fleet layer (DESIGN.md §13): *observational* on the mesh — the
        # worker set is baked into the compiled SPMD program, so leases
        # can't evict anybody, but capability reports and the structured
        # metrics stream flow into the same sink the simulator uses.
        self.metrics = metrics
        self.fleet = FleetMonitor(fleet, metrics=metrics) if fleet is not None else None
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        n_workers = int(np.prod([sizes[a] for a in worker_axes])) if worker_axes else 1
        if profiles is None:
            profiles = [WorkerProfile(v=1.0, o=0.0)] * n_workers
        if len(profiles) != n_workers:
            raise ValueError(f"{len(profiles)} profiles for {n_workers} workers")
        self.workers = [WorkerView(index=i, profile=p) for i, p in enumerate(profiles)]
        self.now = 0.0
        self.losses: list[tuple[float, float]] = []
        self.engine: ClusterEngine | None = None
        self._round = 0
        self._started = False

        ccfg = CommitConfig(
            tau=tau, local_lr=local_lr, global_lr=global_lr,
            worker_axes=worker_axes, commit_dtype=commit_dtype,
            n_shards=n_shards,
        )
        codec = get_codec(codec) if isinstance(codec, str) else codec
        step = make_train_step(
            task.loss_fn, ccfg, rules,
            mesh=mesh if worker_axes else None,
            batch_spec=batch_spec,
            explicit_momentum=explicit_momentum,
            codec=codec,
            fused_commit=fused_commit,
        )
        self.rules = step.rules
        self.codec = step.codec
        self.fused_commit = step.fused_commit
        # the round's state is dead the moment the new one lands: donate
        # it so params/commit/transport buffers are updated in place.
        # Donated buffers are consumed — the state is built by its own jit,
        # into fresh buffers, so the caller's init_params tree stays valid.
        self.step_fn = jax.jit(step, donate_argnums=step.donate_argnums)
        self.state = self._init_state(step, task.init_params, worker_axes)
        # effective shard count: the plan clamps to the leaf count, and
        # the state's version vector is the ground truth for what ran
        versions = jax.tree.leaves(self.state.shard_versions)
        self.n_shards = int(versions[0].shape[0]) if versions else 1
        # Overlapped per-shard commit (DESIGN.md §16): split the round
        # into one push phase (local scan + encode) and K per-shard
        # decode+apply dispatches issued back-to-back with NO host sync
        # between them — shard k+1's transfer is in flight while shard
        # k's apply runs, exactly the simulator's FIFO pull pipeline.
        # Bit-identical to the monolithic step (the per-shard applies
        # are the same leaf-wise ops make_sharded_apply runs in one jit);
        # only valid where the fused commit is (single worker — with one
        # worker the axes-path shard_map degenerates to the plain jit
        # the push phase uses, so the split round stays exact).
        self.overlap_shards = bool(
            overlap_shards and step.fused_commit and self.n_shards > 1
            and n_workers == 1
        )
        if self.overlap_shards:
            self._init_overlap(step, ccfg, explicit_momentum)
        # Wire accounting: bytes each commit round moves worker→PS (every
        # worker ships one encoded update per round). Measured from the
        # codec's static payload size; the identity/no-codec round ships
        # the dense update.
        per_worker = (
            self.codec.encoded_nbytes(task.init_params)
            if self.codec is not None else dense_nbytes(task.init_params)
        )
        self._per_worker_nbytes = per_worker
        self.bytes_per_round = per_worker * n_workers
        self.bytes_to_ps = 0
        if self.fleet is not None:
            for w in self.workers:
                self.fleet.join(w.index, 0.0, w.profile)

    def _init_state(self, step, params, worker_axes) -> AdspState:
        """The initial state, built in the layout the step returns it:
        per-worker slots one per worker — each device materializes only
        its own slot, never all of them — and everything else replicated.
        A state built anywhere else would change the step's input
        shardings after the first round and recompile it."""
        rep = NamedSharding(self.mesh, P())
        per_worker = (NamedSharding(self.mesh, P(tuple(worker_axes)))
                      if worker_axes else rep)
        abstract = jax.eval_shape(step.init, params)
        shardings = dataclasses.replace(
            jax.tree.map(lambda _: rep, abstract),
            local_state=jax.tree.map(lambda _: per_worker, abstract.local_state),
            transport_state=jax.tree.map(lambda _: per_worker,
                                         abstract.transport_state))
        return jax.jit(step.init, out_shardings=shardings)(params)

    # ------------------------------------------------------- overlapped commit
    def _init_overlap(self, step, ccfg, explicit_momentum: float) -> None:
        from repro.ps import get_commit_rule
        from repro.ps.fused_codec import fused_commit_name

        local_rule, commit_rule = step.rules
        codec = step.codec
        fused_rule = get_commit_rule(
            fused_commit_name(commit_rule.name, codec.name), ccfg,
            backend=commit_rule.backend,
        )

        run = make_local_update(self.task.loss_fn, ccfg, local_rule)

        def push(params, lstate, tstate, microbatches, tau_i):
            ls0 = jax.tree.map(lambda x: x[0], lstate)
            u, ls1, loss = run(params, ls0, microbatches, tau_i)
            ts0 = jax.tree.map(lambda x: x[0], tstate)
            enc, ts1 = codec.encode(u, ts0)
            return (enc, jax.tree.map(lambda x: x[None], ls1),
                    jax.tree.map(lambda x: x[None], ts1), loss)

        def pull(p_k, c_k, e_k):
            return fused_rule.apply(p_k, c_k, e_k, explicit_momentum)

        # local/transport slots die with the round: donate them; params
        # feed the per-shard pulls so they are donated there instead
        # (each leaf belongs to exactly one shard). One compiled pull
        # variant per shard shape; K stays small.
        self._push_fn = jax.jit(push, donate_argnums=(1, 2))
        self._pull_fn = jax.jit(pull, donate_argnums=(0, 1))
        self._plan = ShardPlan.build(self.state.params, self.n_shards)
        self._is_payload = fused_rule.is_payload

    def _commit_overlapped(self, mbs, tau_arr):
        """One commit round as push + K per-shard pulls, dispatched with
        no host sync in between: shard k+1's payload transfer is issued
        while shard k's fused decode+apply runs (the device queue
        pipelines them), mirroring the edgesim's FIFO pull pipeline.
        ``run_round`` syncs once at the round boundary via the loss."""
        st = self.state
        tau_i = jnp.asarray(int(tau_arr[0]), jnp.int32)
        enc, lstate, tstate, loss = self._push_fn(
            st.params, st.local_state, st.transport_state, mbs, tau_i)
        p_leaves, treedef = jax.tree.flatten(st.params)
        c_leaves = jax.tree.leaves(st.commit_state)
        e_leaves, _ = jax.tree_util.tree_flatten(enc, is_leaf=self._is_payload)
        new_p = list(p_leaves)
        new_c = list(c_leaves)
        for k in range(self._plan.n_shards):
            idx = self._plan.shard_leaf_indices(k)
            np_k, nc_k = self._pull_fn(
                [p_leaves[i] for i in idx],
                [c_leaves[i] for i in idx] if c_leaves else (),
                [e_leaves[i] for i in idx],
            )
            for i, leaf in zip(idx, np_k):
                new_p[i] = leaf
            if c_leaves:
                for i, leaf in zip(idx, nc_k):
                    new_c[i] = leaf
        params = jax.tree.unflatten(treedef, new_p)
        cstate = (jax.tree.unflatten(treedef, new_c) if c_leaves
                  else st.commit_state)
        versions = st.shard_versions
        if jax.tree.leaves(versions):
            versions = versions + 1
        self.state = AdspState(params, cstate, lstate, st.step + 1,
                               tstate, versions)
        return loss

    # ------------------------------------------------------------ backend API
    def bind(self, engine: ClusterEngine) -> None:
        self.engine = engine
        if self.fleet is not None:
            # initial scheduler pass over the join-time capability reports
            # (later passes ride each heartbeat-delivered set_speed report)
            engine.execute(self.fleet.assignments(self.now))

    def wake(self, w) -> None:  # rounds are synchronous; nothing is parked
        pass

    def recent_global_loss(self) -> float | None:
        if not self.losses:
            return None
        return float(np.mean([l for _, l in self.losses[-3:]]))

    def run_window(self, seconds: float) -> tuple[list[float], list[float]]:
        """Alg. 1 probe: run live for ``seconds`` of round time."""
        start = self.now
        rounds = max(int(math.ceil(seconds / self.round_seconds)), 2)
        for _ in range(rounds):
            self.run_round()
        from repro.control.search import pad_probe_samples

        ts = [t for t, _ in self.losses if t >= start]
        ls = [l for t, l in self.losses if t >= start]
        return pad_probe_samples(ts, ls)

    # ---------------------------------------------------------------- rounds
    def tau_per_worker(self) -> np.ndarray:
        """Rate rule → local step counts: τ_i = v_i·(Γ/ΔC_i − O_i), bounded
        to [1, tau]. Γ here is the policy's check period in round time; with
        no check period yet (before the first SetRate) every worker runs the
        full tau."""
        out = np.empty(len(self.workers), np.int64)
        gamma = getattr(self.engine.policy, "gamma", None) if self.engine else None
        for i, w in enumerate(self.workers):
            if gamma is None:
                out[i] = self.tau
                continue
            t = theory.local_steps_between_commits(
                w.profile, gamma, max(w.delta_c_target, 1)
            )
            out[i] = min(max(t, 1), self.tau)
        return out

    def _ensure_started(self) -> None:
        if not self._started:
            self._started = True
            self.engine.start()

    def run_round(self) -> float:
        """One fused commit round; dispatches CommitApplied per worker."""
        self._ensure_started()
        k = self._round
        with span("adsp.round", k, t=self.now) as rnd:
            with span("adsp.control", k, t=self.now):
                tau_arr = self.tau_per_worker()
            rnd.set(tau=int(tau_arr.sum()))
            with span("adsp.data", k, t=self.now):
                mbs = self.task.make_microbatches(k, self.tau, len(self.workers))
            with span("adsp.dispatch", k, t=self.now):
                if self.overlap_shards:
                    loss = self._commit_overlapped(mbs, tau_arr)
                else:
                    self.state, loss = self.step_fn(
                        self.state, mbs, jnp.asarray(tau_arr, jnp.int32))
            self._round += 1
            self.now = self._round * self.round_seconds
            self.bytes_to_ps += self.bytes_per_round
            with span("adsp.sync", k, t=self.now):
                loss = float(loss)
            self.losses.append((self.now, loss))
            if self.metrics is not None:
                self.metrics.record(EvalRecord(t=self.now, loss=loss))
            with span("adsp.control", k, t=self.now):
                for w, t in zip(self.workers, tau_arr):
                    w.steps += int(t)
                    w.steps_since_commit = 0
                    w.commits += 1
                    if self.metrics is not None:
                        # one fused all-reduce round: latency is the round
                        # wall time; the pull is folded into the collective
                        # (0 bytes)
                        self.metrics.record(CommitRecord(
                            t=self.now, worker=w.index, latency=self.round_seconds,
                            push_bytes=float(self._per_worker_nbytes),
                            pull_bytes=0.0, stale_shards=0, n_shards=self.n_shards,
                        ))
                    self.engine.commit_applied(w)
        return loss

    # ----------------------------------------------------------------- churn
    def set_speed(self, index: int, v: float) -> None:
        """Mid-run speed shift: re-derives τ_i through the policy."""
        w = self.engine.worker(index)
        w.profile = dataclasses.replace(w.profile, v=v)
        self.engine.speed_changed(w)
        if self.fleet is not None:
            # rounds are synchronous: the capability report lands with the
            # next round's commit rather than on a modelled link
            self.fleet.report(index, self.now, v)
            self.engine.execute(self.fleet.assignments(self.now))

    # ----------------------------------------------------------------- drive
    def train(
        self,
        rounds: int,
        *,
        check_period: float | None = None,
        epoch_rounds: int = 0,
        on_round: Callable[[int, float], None] | None = None,
    ) -> list[tuple[float, float]]:
        """Run ``rounds`` commit rounds with checkpoint/epoch cadence.

        check_period: Γ in round time (fire engine.checkpoint each Γ);
        epoch_rounds: fire engine.epoch_end every N rounds (0 = never —
        note Alg. 1's search consumes probe rounds beyond ``rounds``).
        on_round receives the count of *scheduled* rounds completed
        (1-based, probe rounds excluded) and the round's loss.
        """
        self._ensure_started()
        next_check = check_period if check_period else math.inf
        done = 0
        while done < rounds:
            if epoch_rounds and done and done % epoch_rounds == 0:
                with span("adsp.control", self._round, t=self.now):
                    self.engine.epoch_end()
            loss = self.run_round()
            done += 1
            if on_round is not None:
                on_round(done, loss)
            if self.now >= next_check:
                with span("adsp.control", self._round - 1, t=self.now):
                    self.engine.checkpoint()
                next_check += check_period
        return self.losses
