"""One train-step factory for every ADSP granularity and rule backend.

``make_train_step`` replaces the seed's twice-written local-update/commit
math (the seed's ``make_adsp_step`` + ``make_accum_step`` factories,
both now thin shims over this): one τ-masked microstep scan feeds one
CommitRule apply, with the worker axes deciding whether a shard_map +
pmean wraps it.

Mapping (DESIGN.md §3): one ADSP *worker* = one index along the mesh's
worker axes — a model-parallel group holding a full replica of the
parameters (sharded over ``model`` by GSPMD). Workers run ``tau_i``
local microsteps on their own microbatches with no cross-worker
collective (the no-waiting property), then all commit at once: the
accumulated updates are ``pmean``-ed over the worker axes and applied by
the CommitRule — the PS of Alg. 2 realized as an all-reduce. Microsteps
beyond a worker's τ_i are masked (zero update, zero accumulation, frozen
local-optimizer state), keeping the SPMD program uniform.

Granularities (selected per arch, see DESIGN.md §3):

  * ``data`` / ``pod`` — worker axes exist: shard_map + pmean commit;
  * ``accum`` — no worker axis: the whole mesh is one worker doing
    τ-step accumulation; the commit is a plain state update. The
    ``commit_dtype`` cast only happens on the worker-axes path (it
    shapes the all-reduce; there is no collective to shape in accum).

Everything here is jit/shard_map-compatible pure JAX (the fused backends
lower to Pallas, interpret-mode off-TPU); no host callbacks.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .fused_codec import FUSABLE_CODECS, fused_commit_name
from .rules import LocalRule, UpdateRules, get_commit_rule
from .sharding import ShardPlan
from .state import AdspState, CommitConfig

__all__ = ["make_train_step", "make_local_update", "make_sharded_apply",
           "worker_axes_for"]

Pytree = object


def worker_axes_for(granularity: str, mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """ADSP worker axes for an arch's granularity on a given mesh.

    granularity 'data'  → every (pod×)data index is a worker.
    granularity 'pod'   → each pod is one worker (replica memory too large
                          for a 16-chip model group); on a single-pod mesh
                          this degenerates to 'accum' (no worker axis).
    granularity 'accum' → no worker axis: τ-step gradient accumulation.
    """
    has_pod = "pod" in mesh.axis_names
    if granularity == "data":
        return ("pod", "data") if has_pod else ("data",)
    if granularity == "pod":
        return ("pod",) if has_pod else ()
    if granularity == "accum":
        return ()
    raise ValueError(f"unknown adsp granularity {granularity!r}")


def _axes_spec(axes: tuple[str, ...]) -> P:
    """PartitionSpec sharding a leading dim over all worker axes."""
    return P(axes if len(axes) > 1 else axes[0])


def make_sharded_apply(commit_rule, n_shards: int) -> Callable:
    """The commit apply, shard-sliced per the deterministic ShardPlan
    (DESIGN.md §11): slice params/commit-state/update per shard, apply
    the CommitRule shard by shard, merge. Every built-in CommitRule is
    leaf-wise, so the K-sharded apply is bit-identical to the monolithic
    one — sharding reorganizes what the transport layer sees (per-shard
    payloads, versions), never the numerics. n_shards == 1 returns the
    rule's apply untouched (the monolithic fast path).

    Codec-consuming rules (``commit_rule.is_payload`` set, the fused
    decode+apply path of DESIGN.md §16) take an *encoded* ``u`` whose
    leaves are payload atoms, not params-shaped arrays — those trees are
    flattened under the rule's predicate so the per-shard slices stay
    leaf-aligned with the params."""
    if n_shards <= 1:
        return commit_rule.apply

    def apply(params, cstate, u, momentum):
        plan = ShardPlan.build(params, n_shards)
        p_struct = jax.tree.structure(params)
        # commit state is either params-shaped (momentum_delta: sliced
        # along with the params) or leafless (plain_average: passed
        # through whole); anything else cannot be shard-partitioned.
        c_sliceable = jax.tree.structure(cstate) == p_struct
        if not c_sliceable and jax.tree.leaves(cstate):
            raise ValueError(
                f"commit rule {commit_rule.name!r} carries state that is "
                "neither empty nor params-shaped; it cannot be sharded"
            )
        p_leaves, treedef = jax.tree.flatten(params)
        c_leaves = jax.tree.leaves(cstate) if c_sliceable else None
        u_leaves, _ = jax.tree_util.tree_flatten(
            u, is_leaf=commit_rule.is_payload)
        new_p = list(p_leaves)
        new_c = list(c_leaves) if c_sliceable else cstate
        for k in range(plan.n_shards):
            idx = plan.shard_leaf_indices(k)
            p_k = plan.slice(params, k)
            u_k = [u_leaves[i] for i in idx]
            c_k = [c_leaves[i] for i in idx] if c_sliceable else cstate
            np_k, nc_k = commit_rule.apply(p_k, c_k, u_k, momentum)
            for i, leaf in zip(idx, np_k):
                new_p[i] = leaf
            if c_sliceable:
                for i, leaf in zip(idx, nc_k):
                    new_c[i] = leaf
            else:
                new_c = nc_k
        out_p = jax.tree.unflatten(treedef, new_p)
        out_c = jax.tree.unflatten(treedef, new_c) if c_sliceable else new_c
        return out_p, out_c

    return apply


def make_local_update(
    loss_fn: Callable,
    ccfg: CommitConfig,
    local_rule: LocalRule,
    *,
    remat: bool = False,
) -> Callable:
    """The τ-microstep local-update scan: the per-worker inner loop.

    Returns ``run(params, local_state, microbatches, tau_i) ->
    (U, new_local_state, mean_loss)`` where microbatches is a pytree of
    arrays with leading dim ccfg.tau and tau_i is the worker's active
    step count (int32 scalar; steps ≥ tau_i are masked). U is the
    accumulated update the PS consumes (−Σ ΔW_local; for plain sgd the
    paper's Σ η′·g) and the *local* params advance rule-wise each live
    step (then are discarded — the commit applies U to the pre-scan
    params).
    """
    grad_fn = jax.value_and_grad(loss_fn)
    if remat:
        grad_fn = jax.remat(grad_fn)

    def run(params, local_state, microbatches, tau_i):
        zeros = jax.tree.map(jnp.zeros_like, params)

        def body(carry, xs):
            p, u, ls = carry
            mb, idx = xs
            live = (idx < tau_i).astype(jnp.float32)
            loss, g = grad_fn(p, mb)
            p, u, ls = local_rule.update(p, u, g, ls, live)
            return (p, u, ls), loss * live

        idxs = jnp.arange(ccfg.tau, dtype=jnp.int32)
        (_, u, ls), losses = jax.lax.scan(
            body, (params, zeros, local_state), (microbatches, idxs),
        )
        denom = jnp.maximum(tau_i.astype(jnp.float32), 1.0)
        return u, ls, jnp.sum(losses) / denom

    return run


def make_train_step(
    loss_fn: Callable,
    ccfg: CommitConfig,
    rules: UpdateRules | tuple | None = None,
    *,
    mesh: jax.sharding.Mesh | None = None,
    granularity: str | None = None,
    batch_spec=None,
    explicit_momentum: float = 0.0,
    remat: bool = False,
    codec=None,
    fused_commit: bool = False,
) -> Callable:
    """Build the full train step for any granularity and rule backend.

    train_step(state: AdspState, microbatches, tau_per_worker)
        -> (state, loss)

    * microbatches: pytree, arrays shaped (tau, global_batch, ...); on the
      worker-axes path the batch dim is sharded over the worker axes per
      ``batch_spec`` (default ``P(None, <worker axes>)``).
    * tau_per_worker: int32[num_workers] — ADSP rate rule output; worker w
      runs tau_per_worker[w] live microsteps (≤ ccfg.tau). The accum path
      also accepts a bare scalar.

    ``rules`` is an UpdateRules bundle (default: sgd + momentum_delta on
    the auto backend), a resolved (LocalRule, CommitRule) pair, or None.
    ``granularity`` + ``mesh`` derive the worker axes (overriding
    ``ccfg.worker_axes``); with granularity None the config's axes are
    used as-is. The worker-axes path is manual (shard_map) over exactly
    those axes; the ``model`` axis (and any other mesh axis) stays in
    GSPMD auto mode, so tensor-parallel sharding inside loss_fn keeps
    working untouched.

    ``codec`` is a ``repro.transport`` Codec (or registered name) that
    models the commit transport on the real path: each worker's
    accumulated update U is encoded (folding in the worker's
    error-feedback residual, carried in ``state.transport_state``) and
    decoded before the pmean — exactly what a PS shipping compressed
    payloads computes. None (default) and the identity codec leave the
    arithmetic bit-identical to the no-transport step.

    ``fused_commit=True`` asks for the single-pass decode+apply commit
    (DESIGN.md §16): the PS-side decode and the CommitRule apply run as
    one combined rule (``repro.ps.fused_codec``), skipping a full
    params-sized HBM round trip per commit. The fusion is taken only
    when it is bit-identical to the chain — a fusable elementwise codec
    (int8/bf16), one worker (per-worker int8 scales cannot be folded
    across the worker pmean), a registered ``<rule>@<codec>`` combined
    rule, and float32 ``commit_dtype`` (the chain's cast to commit_dtype
    would otherwise reorder the decode) — and falls back to the chain
    path silently otherwise; ``.fused_commit`` on the returned step
    reports whether the fusion is live.

    The returned callable carries ``.init(params) -> AdspState`` (state
    with rule-owned slots), ``.rules`` (the resolved pair), ``.codec``,
    ``.config`` (the effective CommitConfig), ``.n_workers``,
    ``.fused_commit``, and ``.donate_argnums`` (the state argument —
    what jit should donate on the hot path).
    """
    if isinstance(codec, str):
        from repro.transport import get_codec  # deferred: avoids ps↔transport cycle

        codec = get_codec(codec)
    if granularity is not None:
        if mesh is None and granularity != "accum":
            raise ValueError(
                f"make_train_step: granularity {granularity!r} needs a mesh "
                "to derive the worker axes (only 'accum' runs mesh-free)"
            )
        axes = worker_axes_for(granularity, mesh) if mesh is not None else ()
        ccfg = dataclasses.replace(ccfg, worker_axes=axes)
    axes = tuple(ccfg.worker_axes)
    if axes and mesh is None:
        raise ValueError("make_train_step: mesh is required when worker axes are set")

    if isinstance(rules, (tuple, list)):
        local_rule, commit_rule = rules
        _interpret = None
    else:
        bundle = rules if rules is not None else UpdateRules()
        local_rule, commit_rule = bundle.resolve(ccfg)
        _interpret = bundle.interpret

    if axes:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        n_workers = int(np.prod([sizes[a] for a in axes]))
    else:
        n_workers = 1

    # Fused decode+apply (§16): resolve the combined <rule>@<codec> rule
    # when the fusion preconditions hold (see docstring). The chain path
    # stays the default and the bit-for-bit contract.
    fused_rule = None
    if (fused_commit and codec is not None
            and codec.name in FUSABLE_CODECS
            and n_workers == 1
            and jnp.dtype(ccfg.commit_dtype) == jnp.dtype(jnp.float32)):
        try:
            fused_rule = get_commit_rule(
                fused_commit_name(commit_rule.name, codec.name), ccfg,
                backend=commit_rule.backend, interpret=_interpret)
        except KeyError:
            fused_rule = None  # no combined rule registered: chain path
    use_fused = fused_rule is not None

    # PS sharding (§11): the commit apply is shard-sliced per the
    # deterministic ShardPlan; 1 shard keeps the monolithic apply.
    commit_apply = make_sharded_apply(
        fused_rule if use_fused else commit_rule, ccfg.n_shards)

    def _validate_state(state: AdspState) -> None:
        # Catch a seed-era AdspState.create(params) (momentum-delta-shaped,
        # stateless local rule) paired with other rules early, instead of a
        # tree-structure error deep inside the scan. Runs at trace time.
        p_abs = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype), state.params
        )
        checks = [
            ("commit_state", commit_rule, state.commit_state),
            ("local_state", local_rule, state.local_state),
        ]
        if codec is not None:
            checks.append(("transport_state", codec, state.transport_state))
        # the effective shard count clamps to the leaf count (a 1-leaf
        # model runs monolithic no matter the requested K)
        eff = (ShardPlan.build(p_abs, ccfg.n_shards).n_shards
               if ccfg.n_shards > 1 else 1)
        if eff > 1 and not jax.tree.leaves(state.shard_versions):
            raise ValueError(
                f"AdspState.shard_versions is empty but the step runs "
                f"{eff} PS shards; build states with "
                "make_train_step(...).init(params)"
            )
        for label, rule, got in checks:
            want = jax.tree.structure(jax.eval_shape(rule.init, p_abs))
            if jax.tree.structure(got) != want:
                raise ValueError(
                    f"AdspState.{label} does not match the {rule.name!r} rule's "
                    "state; build states with make_train_step(...).init(params)"
                )

    def _next_versions(state: AdspState):
        # Synchronous commit: every shard is written every round, so all K
        # version counters advance together (the counters matter to
        # *asynchronous* consumers — the edgesim's partial pulls — and to
        # shard-granular checkpoint/serve layers reading this state).
        # Keyed off the state, not ccfg.n_shards: the effective count
        # clamps to the leaf count, which can degenerate to monolithic.
        if not jax.tree.leaves(state.shard_versions):
            return state.shard_versions
        return state.shard_versions + 1

    if axes:
        run = make_local_update(loss_fn, ccfg, local_rule, remat=remat)
        if batch_spec is None:
            batch_spec = P(None, axes if len(axes) > 1 else axes[0])

    def _through_codec(u, tstate):
        """Worker-side encode → PS-side decode of one worker's U, with the
        error-feedback residual threaded through the per-worker slot. A
        no-op (bit-identical u) for codec=None / identity."""
        if codec is None:
            return u, tstate
        ts0 = jax.tree.map(lambda x: x[0], tstate)
        enc, ts1 = codec.encode(u, ts0)
        u = codec.decode(enc, u)
        return u, jax.tree.map(lambda x: x[None], ts1)

    def _encode_codec(u, tstate):
        """Worker-side encode only — the fused commit path consumes the
        payload directly, so there is no PS-side decode pass (§16)."""
        ts0 = jax.tree.map(lambda x: x[0], tstate)
        enc, ts1 = codec.encode(u, ts0)
        return enc, jax.tree.map(lambda x: x[None], ts1)

    if axes:
        def _sharded_body(params, cstate, lstate, tstate, step,
                          microbatches, tau_per_worker):
            # tau_per_worker arrives sharded over the worker axes: this
            # shard holds exactly the one entry belonging to this worker.
            tau_i = tau_per_worker[0]
            ls0 = jax.tree.map(lambda x: x[0], lstate)
            u, ls1, loss = run(params, ls0, microbatches, tau_i)
            loss = jax.lax.pmean(loss, axes)
            if use_fused:
                # single worker: the payload IS the worker-mean update, so
                # the fused rule decodes+applies it in one pass (§16)
                enc, tstate_out = _encode_codec(u, tstate)
                new_p, new_c = commit_apply(params, cstate, enc,
                                            explicit_momentum)
            else:
                # ---- transport: what actually crosses the link ----
                u, tstate_out = _through_codec(u, tstate)
                # ---- the commit: PS apply as all-reduce over workers ----
                cd = jnp.dtype(ccfg.commit_dtype)
                u = jax.tree.map(lambda x: x.astype(cd), u)
                u = jax.lax.pmean(u, axes)
                new_p, new_c = commit_apply(params, cstate, u,
                                            explicit_momentum)
            lstate_out = jax.tree.map(lambda x: x[None], ls1)
            return new_p, new_c, lstate_out, tstate_out, step + 1, loss

        # params/commit-state replicated across worker axes (manual);
        # local/transport state sharded one slot per worker; model-axis
        # sharding is handled by auto GSPMD outside the manual set.
        rep = P()
        wspec = _axes_spec(axes)
        sharded = jax.shard_map(
            _sharded_body,
            mesh=mesh,
            in_specs=(rep, rep, wspec, wspec, rep, batch_spec, wspec),
            out_specs=(rep, rep, wspec, wspec, rep, rep),
            axis_names=set(axes),
            check_vma=False,
        )

        def train_step(state: AdspState, microbatches, tau_per_worker):
            _validate_state(state)
            p, c, l, t, s, loss = sharded(
                state.params, state.commit_state, state.local_state,
                state.transport_state, state.step, microbatches, tau_per_worker,
            )
            return AdspState(p, c, l, s, t, _next_versions(state)), loss

    else:
        run = make_local_update(loss_fn, ccfg, local_rule, remat=remat)

        def train_step(state: AdspState, microbatches, tau_per_worker):
            _validate_state(state)
            tau_i = jnp.reshape(jnp.asarray(tau_per_worker, jnp.int32), (-1,))[0]
            ls0 = jax.tree.map(lambda x: x[0], state.local_state)
            u, ls1, loss = run(state.params, ls0, microbatches, tau_i)
            if use_fused:
                enc, tstate_out = _encode_codec(u, state.transport_state)
                new_p, new_c = commit_apply(
                    state.params, state.commit_state, enc, explicit_momentum
                )
            else:
                u, tstate_out = _through_codec(u, state.transport_state)
                new_p, new_c = commit_apply(
                    state.params, state.commit_state, u, explicit_momentum
                )
            lstate_out = jax.tree.map(lambda x: x[None], ls1)
            return AdspState(new_p, new_c, lstate_out, state.step + 1,
                             tstate_out, _next_versions(state)), loss

    # version-vector length follows the plan's clamped shard count (a
    # tree with fewer leaves than requested shards gets one per leaf)
    train_step.init = lambda params: AdspState.create(
        params, rules=(local_rule, commit_rule), n_workers=n_workers,
        codec=codec,
        n_shards=(ShardPlan.build(params, ccfg.n_shards).n_shards
                  if ccfg.n_shards > 1 else 1),
    )
    train_step.rules = (local_rule, commit_rule)
    train_step.codec = codec
    train_step.config = ccfg
    train_step.n_workers = n_workers
    train_step.n_shards = ccfg.n_shards
    train_step.fused_commit = use_fused
    train_step.donate_argnums = (0,)  # the AdspState: safe to donate per round
    return train_step
