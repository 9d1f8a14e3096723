"""ADSP training cells: one ``MeshBackend`` driven as ``repro.launch.train``
drives it, with the commit-rate search off.

Set-up builds the trainer on the benchmark's weights and token stream,
drives it through the traffic file's ``checked_rounds`` rounds (which
compile or load the step) with a copy of the parameters after the first
and the last of them, and hands the same trainer to the window. The
window runs whole rounds until ``--seconds`` have passed.

Correctness: the plain reference (``reference/adsp.py`` over the
configuration's model) runs the checked rounds from the same weights on
the same rows with the same τ_i, and two numbers are compared:

* ``update1_gap``: the first update as the parameter server applied it,
  Δ₁, read from the commit state after the first round (the decoded
  int8 update times the global rate, before the bf16 parameters store
  it), by the worst leaf: |‖Δ₁‖ − ‖Δ₁ref‖| over the larger of ‖Δ₁ref‖
  and the median leaf's ‖Δ₁ref‖. Not W₁ − W₀: most of an update lies
  under half a bf16 step of its weights, so the stored change of a leaf
  is a step function of the codec's one scale per leaf: a 0.5 % move of
  that scale moved one leaf's by 20 % (PERF.md);
* ``change3_gap``: the same for the parameters' change W₃ − W₀, as the
  state that the fourth round starts from stores them.

Leaves the reference leaves all but unmoved (‖Δ₁ref‖ under 1/1000 of
the median leaf's) are left out of both. The first round's loss gap,
|program − reference|, is reported beside them and not compared: no
control or fault reads far enough above the program on it (PERF.md).

``make_trainer`` takes no weights and no token stream, so ``_trainer``
repeats its construction with the benchmark's.
"""

from __future__ import annotations

import functools
import gc
import math
import time

import numpy as np

from chipbench import harness, traffic_gen
from chipbench.flops import dense_train_flops_per_token
from chipbench.reference.adsp import leaf_norms, run_rounds

NEGLIGIBLE = 1e-3  # of the median leaf's first update: moved by round-off alone
REPORTED = ("loss1_gap",)  # readings printed beside the compared ones, never judged


def tree_norms(tree) -> list[float]:
    """Per leaf, the float32 norm of a tree on the device."""
    import jax
    import jax.numpy as jnp

    return [float(jnp.linalg.norm(x.astype(jnp.float32).ravel())) for x in jax.tree.leaves(tree)]


class _WindowClosed(Exception):
    pass


def _trainer(cfg, mesh, params, t: dict, seed: int):
    import jax
    import jax.numpy as jnp
    from repro.cluster import ADSP, ClusterEngine
    from repro.cluster.mesh_backend import MeshBackend
    from repro.control.theory import WorkerProfile
    from repro.launch.mesh import worker_axes_for
    from repro.launch.train import build_mesh_task, unmet_requests
    from repro.models.layers import default_rules
    from repro.ps import UpdateRules
    from repro.transport import get_codec

    worker_axes = worker_axes_for(cfg.adsp_granularity, mesh)
    gb = t["workers"] * t["batch_per_worker"]
    # what launch.steps._rules_for gives a ("data",) mesh whose one axis
    # is the worker axis: no auto data axis
    task = build_mesh_task(cfg, default_rules("model", None), seq=t["seq"], batch=gb,
                           attn_impl=t["attn_impl"])

    def microbatches(round_idx, tau, _n):
        with jax.profiler.TraceAnnotation("bench.make_batch"):
            toks = traffic_gen.train_tokens(seed, round_idx, tau, gb, t["seq"], cfg.vocab_size)
            return {"tokens": jnp.asarray(toks)}

    task.make_microbatches = microbatches
    task.init_params = params
    backend = MeshBackend(
        task, mesh, worker_axes=worker_axes, tau=t["tau"], local_lr=t["local_lr"],
        global_lr=t["global_lr"],
        profiles=[WorkerProfile(v=float(v), o=0.0) for v in t["speeds"]],
        rules=UpdateRules(backend=t["backend"]),
        codec=get_codec(t["codec"], backend=t["backend"]),
        fused_commit=t["fused_commit"])
    task.init_params = jax.eval_shape(lambda: params)
    explicit = None if t["backend"] == "auto" else t["backend"]
    unmet = unmet_requests(backend, rule_backend=explicit, codec_backend=explicit,
                           fused_commit=t["fused_commit"])
    if unmet:
        raise RuntimeError("the program departs from the traffic file: " + "; ".join(unmet))
    policy = ADSP(gamma=8.0, search=False, probe_seconds=3.0, max_probes=4,
                  search_mode="epoch", drift_threshold=0.25, drift_cooldown=32.0,
                  reward_model="log_slope")
    engine = ClusterEngine(policy, backend)
    return backend, engine, policy


def norm_gap(prog: list[float], ref: list[float], keep: list[bool]) -> float:
    """Worst leaf's |‖prog‖ − ‖ref‖| over max(‖ref‖, median leaf ‖ref‖)."""
    kept = [(p, r) for p, r, k in zip(prog, ref, keep) if k]
    med = float(np.median([r for _, r in kept]))
    return max(abs(p - r) / max(r, med) for p, r in kept)


def loss1_gap(prog: dict, ref: dict) -> float:
    """|program − reference| loss of the first round, reported only."""
    return abs(prog["loss"][0] - ref["loss"][0])


def compare(prog: dict, ref: dict) -> dict:
    """The compared readings from the program's and the reference's
    per-leaf update norms (``update1``, ``change3``)."""
    med1 = float(np.median(ref["update1"]))
    keep = [r >= NEGLIGIBLE * med1 for r in ref["update1"]]
    return {
        "update1_gap": norm_gap(prog["update1"], ref["update1"], keep),
        "change3_gap": norm_gap(prog["change3"], ref["change3"], keep),
    }


def reference_side(ctx, P0, batches, taus, mode="f32", fault=None) -> dict:
    """Losses and per-leaf update norms of the plain reference (``mode``
    'fp8' and a ``fault`` give the control and the planted faults)."""
    ref = ctx.reference()
    t = ctx.traffic
    norms = {}

    def on_round(r, params, applied):
        if r == 0:
            norms["update1"] = applied
        if r == len(batches) - 1:
            norms["change3"] = leaf_norms(params, P0)

    losses = run_rounds(functools.partial(ref.loss, m=ctx.model, mode=mode), P0, batches,
                        taus, local_lr=t["local_lr"], global_lr=t["global_lr"],
                        fault=fault, on_round=on_round)
    return {"loss": losses, **norms}


def checked_setup(ctx) -> dict:
    """Set-up: the benchmark's weights, the trainer on them, and the
    checked rounds through the window's own call and feed, with a copy
    of the parameters before and after the last, and the norms of the
    update that the first committed."""
    import jax
    from repro.launch.mesh import make_mesh

    t, m = ctx.traffic, ctx.model
    n = t["workers"]
    cfg = harness.model_config(m)
    devices = jax.devices()[:n]
    mesh = make_mesh((n,), ("data",), devices=devices)
    params = ctx.reference().init_params(traffic_gen.jax_key(ctx.seed), m)
    harness.check_layout(params, cfg)
    P0 = jax.device_get(params)
    backend, engine, policy = _trainer(cfg, mesh, params, t, ctx.seed)
    del params

    checked = t["checked_rounds"]
    st = {"cfg": cfg, "mesh": mesh, "devices": devices, "backend": backend,
          "engine": engine, "policy": policy, "P0": P0, "snaps": {}, "taus": [],
          "losses": [], "prev": [w.steps for w in backend.workers]}

    def on_checked(k, loss):
        st["taus"].append(steps_done(st))
        st["losses"].append(loss)
        state = backend.state
        if k == 1:
            if jax.tree.structure(state.commit_state) != jax.tree.structure(state.params):
                raise RuntimeError("the commit state is not the applied update the check reads")
            st["update1"] = tree_norms(state.commit_state)
        if k == checked:
            st["snaps"][k] = jax.device_get(state.params)

    with jax.set_mesh(mesh):
        backend.train(checked, check_period=policy.gamma, on_round=on_checked)
        jax.block_until_ready(backend.state)
    return st


def steps_done(st) -> list:
    """Local steps each worker committed since the last call."""
    now = [w.steps for w in st["backend"].workers]
    d = [a - b for a, b in zip(now, st["prev"])]
    st["prev"] = now
    return d


def program_side(st) -> dict:
    snaps, P0 = st["snaps"], st["P0"]
    return {"loss": st["losses"], "update1": st["update1"],
            "change3": leaf_norms(snaps[max(snaps)], P0)}


def checked_batches(ctx, st) -> list:
    t = ctx.traffic
    return [traffic_gen.train_tokens(ctx.seed, r, t["tau"], t["workers"] * t["batch_per_worker"],
                                     t["seq"], st["cfg"].vocab_size)
            for r in range(t["checked_rounds"])]


def free_program(st) -> None:
    for k in ("backend", "engine", "policy"):
        st.pop(k, None)
    gc.collect()


def run(ctx) -> dict:
    import jax

    t = ctx.traffic
    st = checked_setup(ctx)
    backend, policy = st["backend"], st["policy"]

    # the window: whole rounds until --seconds have passed
    per_step = t["batch_per_worker"] * t["seq"]
    count = {"rounds": 0, "tokens": 0, "failed": 0}
    box = {}

    def on_window(k, loss):
        with jax.profiler.TraceAnnotation("bench.on_round"):
            count["rounds"] += 1
            count["tokens"] += sum(steps_done(st)) * per_step
            count["failed"] += not math.isfinite(loss)
            if time.perf_counter() - box["t0"] >= ctx.seconds:
                raise _WindowClosed

    with jax.set_mesh(st["mesh"]), harness.traced_window(ctx.trace) as tw:
        box["t0"] = time.perf_counter()
        setup_s = box["t0"] - ctx.t_start
        try:
            backend.train(10**9, check_period=policy.gamma, on_round=on_window)
        except _WindowClosed:
            pass
        jax.block_until_ready(backend.state)
        window_s = time.perf_counter() - box["t0"]
    mem = harness.memory_peak(st["devices"])
    tokens_per_s = count["tokens"] / window_s
    del backend, policy
    free_program(st)

    prog = program_side(st)
    t_ref = time.perf_counter()
    ref = reference_side(ctx, st["P0"], checked_batches(ctx, st), st["taus"])
    readings = compare(prog, ref)
    return {
        "attempted": count["rounds"], "failed": count["failed"],
        "metrics": {"train_tokens_per_s": (tokens_per_s, "tokens/s"), "setup_s": (setup_s, "s")},
        "readings": readings, "memory_peak_bytes": mem, "trace_dir": tw["dir"],
        "window_s": window_s,
        "notes": {"taus": st["taus"], "loss1_gap": loss1_gap(prog, ref),
                  "losses": st["losses"], "ref_losses": ref["loss"],
                  "reference_s": time.perf_counter() - t_ref,
                  "update1_norms": [prog["update1"], ref["update1"]]},
        "facts": {"rounds": count["rounds"], "committed_tokens": count["tokens"],
                  "tokens_per_s": tokens_per_s, "seq": t["seq"],
                  "batch_per_worker": t["batch_per_worker"],
                  "flops_per_token": dense_train_flops_per_token(ctx.model, t["seq"]),
                  "model": ctx.model},
    }
