"""Sharded-PS benchmark: pull bytes and convergence time vs shard count K
on a link-bound fleet (DESIGN.md §11).

One ADSP run per K, identical task/fleet/policy/seed. K=1 is the
monolithic PS (bit-identical to the pre-sharding stack): every pull
ships the full dense model. K>1 partitions the model into versioned
shards — per-shard push payloads pipeline FIFO over each worker's link
and pulls fetch only shards whose PS version moved past the worker's
local copy, so ``bytes_from_ps`` shrinks while convergence time stays
equal or improves (stale shards ship sooner, fresh shards don't ship at
all). Push bytes (``bytes_to_ps``) are invariant in K: every built-in
codec is leaf-wise, so the per-shard encodes partition the lumped one.

These rows are the CI smoke gate for the sharding layer.
"""

from __future__ import annotations

import time

from repro.cluster import make_policy
from repro.edgesim import SimConfig, Simulator
from repro.edgesim.profiles import ratio_profiles, with_links
from repro.edgesim.tasks import cnn_task
from repro.transport import dense_nbytes

from .common import GAMMA, row


def _shard_rows(full: bool) -> list[str]:
    m = 3
    target = 0.75
    max_seconds = 4000.0
    shard_counts = (1, 2, 4, 8, 16) if full else (1, 2, 4, 8)
    rows = []
    baseline_pull = baseline_per_commit = None
    for k in shard_counts:
        task = cnn_task(m, width=8)
        # strongly link-bound: a dense transfer costs ~8 virtual seconds
        # (40× the fixed o/2) — the regime where pull time is the dominant
        # commit cost and partial pulls pay off directly
        dense = dense_nbytes(task.init_params)
        profiles = with_links(
            ratio_profiles((1,) * (m - 1) + (3,), base_v=1.0, o=0.2),
            bandwidth=dense / 16.0, latency=0.01,
        )
        cfg = SimConfig(gamma=GAMMA, epoch_seconds=200.0, base_batch=32,
                        target_loss=target, max_seconds=max_seconds,
                        local_lr=0.05, eval_interval=2.0)
        t0 = time.time()
        sim = Simulator(
            task, profiles, make_policy("adsp", search=False, gamma=GAMMA),
            cfg, codec="identity", n_shards=k,
        )
        res = sim.train()
        wall = time.time() - t0
        per_commit = res.bytes_from_ps / max(res.total_commits, 1)
        if k == 1:
            baseline_pull = res.bytes_from_ps
            baseline_per_commit = per_commit
        rows.append(row(
            f"shards/K{k}", wall, max(res.elapsed, 1e-9),
            n_shards=sim.n_shards,
            bytes_from_ps=res.bytes_from_ps,
            bytes_to_ps=res.bytes_to_ps,
            pull_ratio=(res.bytes_from_ps / baseline_pull
                        if baseline_pull else float("nan")),
            pull_per_commit_ratio=(per_commit / baseline_per_commit
                                   if baseline_per_commit else float("nan")),
            t_conv=res.convergence_time if res.converged else float("inf"),
            converged=int(res.converged),
            final_loss=float(res.losses[-1]),
            commits=res.total_commits,
            waiting_frac=res.waiting_fraction,
        ))
    return rows


def _overlap_row() -> list[str]:
    """Overlapped per-shard commit on the real mesh backend (§16): one
    ADSP round as a single monolithic fused dispatch vs push + K pull
    dispatches with no host sync between shards. The wall ratio is
    informational (CPU interpret mode has no transfer to hide — the win
    is on TPU where shard k+1's payload moves while shard k applies);
    the ``overlap_matches`` gate pins that both schedules produce the
    same params to a few ulps (bit-equality across the two jit
    partitionings is up to the compiler: splitting push from pull shifts
    XLA fusion decisions inside the local scan)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.cluster import ADSP, ClusterEngine
    from repro.cluster.mesh_backend import MeshBackend, MeshTask
    from repro.launch.mesh import make_mesh

    from .common import time_fn

    rng = np.random.default_rng(0)
    dim = 256
    x = jnp.asarray(rng.normal(size=(32, dim)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(32, 1)), jnp.float32)

    def loss_fn(params, batch):
        xb, yb = batch
        return jnp.mean((xb @ params["w1"] @ params["w2"] - yb) ** 2)

    task = MeshTask(
        init_params={"w1": jnp.asarray(rng.normal(size=(dim, dim)) * 0.05,
                                       jnp.float32),
                     "w2": jnp.asarray(rng.normal(size=(dim, 1)) * 0.05,
                                       jnp.float32)},
        loss_fn=loss_fn,
        make_microbatches=lambda r, tau, n: (jnp.stack([x] * tau),
                                             jnp.stack([y] * tau)),
    )
    mesh = make_mesh((1,), ("data",))
    walls, params = {}, {}
    for name, overlap in (("mono", False), ("overlap", True)):
        backend = MeshBackend(task, mesh, tau=2, codec="bf16", n_shards=2,
                              fused_commit=True, overlap_shards=overlap)
        ClusterEngine(ADSP(search=False, gamma=4.0), backend)
        assert backend.fused_commit and backend.overlap_shards == overlap
        with jax.set_mesh(mesh):
            walls[name] = time_fn(backend.run_round, iters=5, warmup=2)
        params[name] = backend.state.params
    match = all(
        np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                    rtol=1e-5, atol=1e-6)
        for a, b in zip(jax.tree.leaves(params["mono"]),
                        jax.tree.leaves(params["overlap"])))
    return [row(
        "shards/overlap_mesh", walls["overlap"], 1.0,
        overlap_matches=int(match),
        overlap_wall_ratio=walls["overlap"] / walls["mono"],
        n_shards=2, pull_dispatches_per_round=2,
    )]


def main(full: bool = False) -> list[str]:
    return _shard_rows(full) + _overlap_row()
