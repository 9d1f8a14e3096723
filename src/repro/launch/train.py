"""Training launcher: runs real ADSP training of any registered arch on
whatever devices exist (CPU host devices for development, TPU mesh in
production), with the full control plane: ADSP rate rule → τ_i assignment
→ periodic commit-rate search on the live loss curve (Alg. 1 on the
cluster).

The control plane is the *same* code the edge simulator uses: a
``repro.cluster.ADSP`` policy driven by a ``ClusterEngine`` over the
``repro.cluster.mesh_backend.MeshBackend`` (DESIGN.md §4) — Alg. 1 and
Alg. 2 exist exactly once in the repo.

Usage (CPU dev, reduced config):
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-moe-a2.7b \
        --smoke --steps 50 --seq 128 --batch 8 --tau 4

Commit transport: ``--codec {identity,int8,bf16,top_k}`` compresses the
per-commit update payload through ``repro.transport`` (with error
feedback; ``--codec-backend fused`` routes encode/decode through the
Pallas kernels); the header line reports the measured MB/round to the PS.
``--ps-shards K`` partitions the PS into K versioned shards (DESIGN.md
§11): the commit applies shard by shard per the deterministic ShardPlan
and the state carries per-shard version counters; 1 (default) is the
monolithic PS, bit-identical to the unsharded stack.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_train_state
from repro.cluster import ADSP, ClusterEngine
from repro.control import reward_model_names
from repro.cluster.mesh_backend import MeshBackend, MeshTask
from repro.configs import get_config, get_smoke
from repro.control.theory import WorkerProfile
from repro.data.synthetic import lm_tokens
from repro.fleet import FleetConfig, JsonlSink, LeaseConfig, scheduler_names
from repro.fleet.metrics import span_stream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh, worker_axes_for
from repro.models import lm
from repro.models.attention import resolve_attn_impl
from repro.models.config import ModelConfig
from repro.ps import UpdateRules, add_rule_args, add_shard_args, rules_from_args
from repro.transport import add_codec_args, codec_from_args

__all__ = ["build_mesh_task", "cut_layers", "make_trainer", "unmet_requests", "main"]


def build_mesh_task(cfg: ModelConfig, rules, *, seq: int, batch: int,
                    seed: int = 0, attn_impl: str | None = None) -> MeshTask:
    """Bind an LM architecture + data stream into a MeshTask.

    ``attn_impl`` follows ``models.attention.resolve_attn_impl``: 'ref'
    (pure-JAX blockwise scan) / 'flash' (Pallas kernel); None picks per
    family — flash is the granite-family default on TPU.
    """
    impl = resolve_attn_impl(attn_impl, cfg.name)

    def loss_fn(params, mb):
        return lm.lm_loss(cfg, params, mb, rules=rules, attn_impl=impl,
                          remat=False)

    def make_microbatches(round_idx: int, tau: int, _n_workers: int):
        toks = lm_tokens(seed, round_idx * 7919, tau * batch, seq,
                         cfg.vocab_size)[:, :-1]
        return {"tokens": jnp.asarray(toks.reshape(tau, batch, seq), jnp.int32)}

    return MeshTask(
        init_params=None,  # filled by make_trainer (needs dtype cast)
        loss_fn=loss_fn,
        make_microbatches=make_microbatches,
        name=f"train:{cfg.name}",
    )


def cut_layers(cfg: ModelConfig, n: int) -> ModelConfig:
    """``cfg`` cut to its first ``n`` layers, every width kept: the depth
    cut that fits a published model on fewer chips (the layers left out
    would sit on further pipeline stages). At least one whole period of
    the layer pattern stays, so every block kind is present."""
    period = len(cfg.layer_pattern)
    if not period <= n <= cfg.num_layers:
        raise ValueError(
            f"--layers {n}: {cfg.name} needs {period}..{cfg.num_layers} "
            "layers (at least one whole period of its layer pattern)")
    return dataclasses.replace(cfg, num_layers=n)


def make_trainer(cfg: ModelConfig, mesh, *, tau: int, seq: int, batch: int,
                 local_lr: float, global_lr: float, seed: int = 0,
                 gamma_rounds: float = 8.0, search_every: int = 0,
                 speeds=None,
                 update_rules: UpdateRules | None = None,
                 codec=None,
                 n_shards: int = 1,
                 fused_commit: bool = False,
                 overlap_shards: bool = False,
                 attn_impl: str | None = None,
                 search_mode: str = "epoch",
                 drift_threshold: float = 0.25,
                 reward_model: str = "log_slope",
                 fleet: FleetConfig | None = None,
                 metrics=None,
                 ) -> tuple[MeshBackend, ClusterEngine, ADSP]:
    """Build the (backend, engine, policy) triple for an arch on a mesh."""
    from repro.launch.steps import _rules_for

    worker_axes = worker_axes_for(cfg.adsp_granularity, mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_workers = int(np.prod([sizes[a] for a in worker_axes])) if worker_axes else 1
    if batch % n_workers:
        raise ValueError(
            f"batch {batch} does not split over {n_workers} ADSP workers: "
            "each worker takes batch/workers sequences per microstep")
    rules = _rules_for(mesh, worker_axes)
    task = build_mesh_task(cfg, rules, seq=seq, batch=batch, seed=seed,
                           attn_impl=attn_impl)
    task.init_params = lm.lm_init_cast(jax.random.PRNGKey(seed), cfg)
    speeds = speeds if speeds is not None else [1.0] * n_workers
    profiles = [WorkerProfile(v=float(v), o=0.0) for v in speeds]
    backend = MeshBackend(
        task, mesh, worker_axes=worker_axes, tau=tau,
        local_lr=local_lr, global_lr=global_lr, profiles=profiles,
        rules=update_rules, codec=codec, n_shards=n_shards,
        fused_commit=fused_commit, overlap_shards=overlap_shards,
        fleet=fleet, metrics=metrics,
    )
    # the backend's state holds the params now; keep only their shapes so
    # a second full copy of the model does not stay resident
    task.init_params = jax.eval_shape(lambda: task.init_params)
    # drift mode stays armed even with no epoch cadence configured: the
    # detector, not the epoch clock, decides when to search
    policy = ADSP(
        gamma=gamma_rounds,
        search=bool(search_every) or search_mode in ("drift", "both"),
        probe_seconds=3.0, max_probes=4,
        search_mode=search_mode, drift_threshold=drift_threshold,
        drift_cooldown=4 * gamma_rounds, reward_model=reward_model,
    )
    engine = ClusterEngine(policy, backend, metrics=metrics)
    return backend, engine, policy


def unmet_requests(backend: MeshBackend, *, rule_backend: str | None,
                   codec_backend: str | None,
                   fused_commit: bool) -> list[str]:
    """What was explicitly asked for but resolved to something else.

    The library falls back where a fused implementation is missing
    (``repro.ps.rules``, ``repro.transport.codec``, the chain commit);
    an entry point may not. Only ``auto`` may choose."""
    out = []
    lr_rule, cr_rule = backend.rules
    if rule_backend == "fused" and "reference" in (lr_rule.backend,
                                                   cr_rule.backend):
        out.append(f"--rule-backend fused resolved to "
                   f"{lr_rule.name}[{lr_rule.backend}] + "
                   f"{cr_rule.name}[{cr_rule.backend}]")
    codec = backend.codec
    if codec_backend == "fused" and (codec is None or codec.backend != "fused"):
        name = codec.name if codec is not None else "none"
        out.append(f"--codec-backend fused: codec {name!r} has no fused "
                   "implementation")
    if fused_commit and not backend.fused_commit:
        out.append("--fused-commit did not take effect: it needs --codec "
                   "int8|bf16, one worker and float32 commits")
    return out


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--layers", type=int, default=0,
                   help="keep only the first N layers at published widths "
                        "(a depth cut, printed as a reduction; 0 = all)")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--tau", type=int, default=4)
    p.add_argument("--local-lr", type=float, default=0.02)
    p.add_argument("--global-lr", type=float, default=1.0)
    p.add_argument("--gamma-rounds", type=float, default=8.0,
                   help="check period Γ in commit rounds")
    p.add_argument("--search-every", type=int, default=0,
                   help="run Alg. 1 search every N commits (0 = off)")
    p.add_argument("--search-mode", default="epoch",
                   choices=["epoch", "drift", "both"],
                   help="when to re-search: on the epoch clock (paper), "
                        "on detected fleet drift, or both")
    p.add_argument("--drift-threshold", type=float, default=0.25,
                   help="speed-fraction TV distance triggering a drift "
                        "re-search (--search-mode drift|both)")
    p.add_argument("--reward-model", default="log_slope",
                   choices=reward_model_names(),
                   help="probe-window reward model (repro.control registry)")
    p.add_argument("--lease-ttl", type=float, default=0.0,
                   help="fleet lease TTL in round time (0 = no fleet layer)")
    p.add_argument("--heartbeat-period", type=float, default=0.0,
                   help="heartbeat period in round time (default ttl/3)")
    p.add_argument("--scheduler", default="",
                   choices=[""] + scheduler_names(),
                   help="capability-aware device scheduler (repro.fleet); "
                        "empty leaves batch fractions to the policy")
    p.add_argument("--metrics", default="",
                   help="write the structured fleet metrics stream (JSONL) "
                        "to this path; summarize with tools/fleet_report.py")
    p.add_argument("--fused-commit", action="store_true",
                   help="single-pass decode+apply PS commit (DESIGN.md "
                        "§16); needs --codec int8|bf16, one worker and "
                        "float32 commits, and exits with an error otherwise")
    p.add_argument("--overlap-shards", action="store_true",
                   help="with --fused-commit and --ps-shards K>1: issue "
                        "per-shard pull/decode dispatches back-to-back "
                        "with no host sync between shards")
    p.add_argument("--attn-impl", default=None, choices=["ref", "flash"],
                   help="training attention: 'ref' pure-JAX blockwise, "
                        "'flash' Pallas kernel (default: flash for the "
                        "granite family on TPU, ref elsewhere)")
    p.add_argument("--checkpoint", default="")
    p.add_argument("--seed", type=int, default=0)
    add_rule_args(p)
    add_codec_args(p)
    add_shard_args(p)
    args = p.parse_args(argv)

    enable_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    full_layers = cfg.num_layers
    if args.layers:
        cfg = cut_layers(cfg, args.layers)
    # one ADSP worker per device and no tensor parallelism: a Pallas
    # kernel compiles only under a shard_map manual over every mesh axis
    n = len(jax.devices())
    mesh = make_mesh((n,), ("data",))
    rules = rules_from_args(args)
    codec = codec_from_args(args)
    fleet = None
    if args.lease_ttl > 0 or args.scheduler:
        ttl = args.lease_ttl if args.lease_ttl > 0 else 3.0 * args.gamma_rounds
        period = args.heartbeat_period if args.heartbeat_period > 0 else ttl / 3.0
        fleet = FleetConfig(
            lease=LeaseConfig(ttl=ttl, heartbeat_period=period),
            scheduler=args.scheduler or None,
        )
    metrics = JsonlSink(args.metrics) if args.metrics else None
    backend, engine, policy = make_trainer(
        cfg, mesh, tau=args.tau, seq=args.seq, batch=args.batch,
        local_lr=args.local_lr, global_lr=args.global_lr, seed=args.seed,
        gamma_rounds=args.gamma_rounds, search_every=args.search_every,
        update_rules=rules, codec=codec, n_shards=args.ps_shards,
        fused_commit=args.fused_commit, overlap_shards=args.overlap_shards,
        attn_impl=args.attn_impl,
        search_mode=args.search_mode, drift_threshold=args.drift_threshold,
        reward_model=args.reward_model, fleet=fleet, metrics=metrics,
    )
    unmet = unmet_requests(backend, rule_backend=args.rule_backend,
                           codec_backend=args.codec_backend,
                           fused_commit=args.fused_commit)
    if unmet:
        p.error("; ".join(unmet))
    lr_rule, cr_rule = backend.rules
    if args.layers:
        print(f"# reduced: layers {cfg.num_layers} of {full_layers}, "
              "every width as published")
    print(f"# arch={cfg.name} params={cfg.total_params()/1e6:.1f}M "
          f"workers={len(backend.workers)} tau={args.tau} "
          f"rules={lr_rule.name}+{cr_rule.name}[{cr_rule.backend}] "
          f"codec={backend.codec.name}[{backend.codec.backend}] "
          f"ps_shards={backend.n_shards} "
          f"fused_commit={backend.fused_commit} "
          f"overlap={backend.overlap_shards} "
          f"attn={resolve_attn_impl(args.attn_impl, cfg.name)} "
          f"({backend.bytes_per_round/1e6:.2f} MB/round to PS)")
    t0 = time.time()

    def on_round(rnd, loss):
        if (rnd - 1) % 5 == 0 or rnd == args.steps:
            print(f"step {rnd - 1:4d} loss {loss:.4f} "
                  f"({(time.time() - t0) / rnd:.2f}s/commit)")

    # the round loop's spans join the --metrics stream as training ends
    with jax.set_mesh(mesh), span_stream(metrics):
        backend.train(args.steps, check_period=policy.gamma,
                      epoch_rounds=args.search_every, on_round=on_round)
    print(f"# bytes_to_ps={backend.bytes_to_ps/1e6:.2f} MB "
          f"over {args.steps} rounds")
    for i, tr in enumerate(policy.traces):
        print(f"# search {i}: candidates={tr.candidates} "
              f"rewards={[f'{r:.3g}' for r in tr.rewards]} -> {tr.chosen}")
    if args.checkpoint:
        save_train_state(args.checkpoint, backend.state, step=args.steps,
                         extra={"arch": cfg.name})
        print(f"# saved {args.checkpoint}")
    if metrics is not None:
        metrics.close()
        print(f"# metrics stream -> {args.metrics}")


if __name__ == "__main__":
    main()
