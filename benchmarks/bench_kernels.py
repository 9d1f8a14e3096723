"""Pallas kernel micro-benchmarks (interpret mode on CPU — correctness +
host-side cost only; wall numbers are NOT TPU predictions, the roofline
table carries those)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

from .common import row, time_fn as _time


def main(full: bool = False) -> list[str]:
    rows = []
    rng = np.random.default_rng(0)

    b, s, hq, hkv, d = 1, 256, 4, 2, 64
    q = jnp.asarray(rng.normal(size=(b, s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    t = _time(lambda *a: ops.flash_attention(*a, block_q=128, block_k=128), q, k, v)
    err = float(jnp.max(jnp.abs(
        ops.flash_attention(q, k, v, block_q=128, block_k=128) - ref.flash_attention(q, k, v))))
    rows.append(row("kernels/flash_attention", t, 1.0, max_err=err,
                    shape=f"b{b}s{s}h{hq}d{d}"))

    a_ = jnp.asarray(rng.uniform(0.8, 0.999, size=(2, 512, 256)), jnp.float32)
    b_ = jnp.asarray(rng.normal(size=(2, 512, 256)) * 0.1, jnp.float32)
    t = _time(lambda *x: ops.rglru_scan(*x, block_w=256, block_s=128), a_, b_)
    err = float(jnp.max(jnp.abs(ops.rglru_scan(a_, b_, block_w=256, block_s=128) - ref.rglru_scan(a_, b_))))
    rows.append(row("kernels/rglru_scan", t, 1.0, max_err=err, shape="2x512x256"))

    r = jnp.asarray(rng.normal(size=(1, 256, 2, 16)) * 0.5, jnp.float32)
    kk = jnp.asarray(rng.normal(size=(1, 256, 2, 16)) * 0.5, jnp.float32)
    vv = jnp.asarray(rng.normal(size=(1, 256, 2, 16)) * 0.5, jnp.float32)
    w = jnp.asarray(rng.uniform(0.9, 0.999, size=(1, 256, 2, 16)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(2, 16)) * 0.1, jnp.float32)
    t = _time(lambda *x: ops.rwkv6_scan(*x, block_s=64)[0], r, kk, vv, w, u)
    err = float(jnp.max(jnp.abs(ops.rwkv6_scan(r, kk, vv, w, u, block_s=64)[0]
                                - ref.rwkv6_scan(r, kk, vv, w, u)[0])))
    rows.append(row("kernels/rwkv6_scan", t, 1.0, max_err=err, shape="1x256x2x16"))

    tree = {"w": jnp.asarray(rng.normal(size=(1 << 16,)), jnp.float32)}
    g = jax.tree.map(lambda x: x * 0.3, tree)
    t = _time(lambda *x: ops.accumulate_tree(*x, 0.05), tree, g)
    rows.append(row("kernels/fused_accumulate", t, 1.0, elems=1 << 16))
    d0 = jax.tree.map(jnp.zeros_like, tree)
    t = _time(lambda *x: ops.ps_apply_tree(*x, 0.1, 0.9)[0], tree, d0, g)
    rows.append(row("kernels/fused_ps_apply", t, 1.0, elems=1 << 16))
    rows.extend(_bench_train_step_backends())
    rows.extend(_bench_fused_commit_round())
    return rows


def _bench_fused_commit_round() -> list[str]:
    """The PS pull side of one commit round, chain vs fused (§16): the
    chain is two host dispatches (codec decode, then commit apply); the
    combined ``momentum_delta@int8`` rule is one. ``fused_commit_speedup``
    is a within-run host-time ratio — both sides run in the same process
    seconds apart, so machine speed cancels and CI can gate on it."""
    from repro.ps import CommitConfig, get_commit_rule
    from repro.transport import get_codec

    rng = np.random.default_rng(0)
    n = 1 << 20
    w = {"w": jnp.asarray(rng.normal(size=(n,)), jnp.float32)}
    u = jax.tree.map(lambda x: x * 0.05 + 0.01, w)
    cfg = CommitConfig(tau=1, global_lr=0.7, worker_axes=())
    codec = get_codec("int8", backend="reference")
    enc, _ = jax.jit(codec.encode)(u, jax.tree.map(jnp.zeros_like, u))
    jax.block_until_ready(enc)
    chain_rule = get_commit_rule("momentum_delta", cfg, backend="fused")
    fused_rule = get_commit_rule("momentum_delta@int8", cfg, backend="fused")
    cstate = chain_rule.init(w)
    decode = jax.jit(lambda e: codec.decode(e, w))
    apply_chain = jax.jit(lambda p, c, uu: chain_rule.apply(p, c, uu, 0.9))
    apply_fused = jax.jit(lambda p, c, e: fused_rule.apply(p, c, e, 0.9))

    dispatches = {"ref": 0, "fused": 0}

    def ref_round():
        dispatches["ref"] += 2
        return apply_chain(w, cstate, decode(enc))

    def fused_round():
        dispatches["fused"] += 1
        return apply_fused(w, cstate, enc)

    t_ref = _time(ref_round, iters=5)
    n_ref = dispatches["ref"] / (5 + 1)  # warmup + timed calls
    t_fused = _time(fused_round, iters=5)
    n_fused = dispatches["fused"] / (5 + 1)
    return [row(
        "kernels/fused_commit_round", t_fused, 1.0,
        fused_commit_speedup=t_ref / t_fused,
        dispatch_speedup=n_ref / n_fused,
        dispatches_ref=n_ref, dispatches_fused=n_fused,
        elems=n,
    )]


def _bench_train_step_backends() -> list[str]:
    """The unified train step end-to-end, reference vs Pallas-fused rule
    backend (the fused kernels on their actual hot path, not only as
    isolated ops). Interpret mode on CPU: structure cost only."""
    from repro.launch.mesh import make_mesh
    from repro.ps import CommitConfig, UpdateRules, make_train_step

    def quad_loss(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    rng = np.random.default_rng(0)
    dim = 64
    x = jnp.asarray(rng.normal(size=(32, dim)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(32, 1)), jnp.float32)
    mbs = (jnp.stack([x, x]), jnp.stack([y, y]))
    params = {"w": jnp.asarray(rng.normal(size=(dim, 1)) * 0.1, jnp.float32)}
    cfg = CommitConfig(tau=2, local_lr=0.05, worker_axes=("data",))
    mesh = make_mesh((1,), ("data",))
    tau = jnp.asarray([2], jnp.int32)

    out = []
    with jax.set_mesh(mesh):
        for backend in ("reference", "fused"):
            step_fn = make_train_step(
                quad_loss, cfg, UpdateRules(backend=backend), mesh=mesh)
            state = step_fn.init(params)
            step = jax.jit(step_fn)
            t = _time(lambda s: step(s, mbs, tau)[1], state)
            out.append(row(f"ps/train_step_sgd_{backend}", t, 1.0,
                           tau=2, dim=dim))
    return out
