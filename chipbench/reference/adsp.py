"""Plain reference of ADSP commit rounds, as the traffic file states them:
each worker runs its τ_i local sgd steps from the committed parameters
(p ← p − η′·g) and accumulates its update U = Σ η′·g, sends U through
the int8 codec with error feedback (e = U + r; scale = max|e| / 127;
q = round(e / scale) clipped to ±127; r ← e − q·scale), and the
parameter server applies the workers' mean decoded update with global
learning rate η (W ← W − η·mean; the momentum of the paper's Eqn. 1 is
0 in these cells).

All arithmetic is float32. Parameters are stored in the configuration's
dtype, as the configuration states: each local step and each commit
rounds the parameters it writes to that dtype once.

One device holds one worker's parameters, update, gradients and
activations; each worker's residual, the sum of several workers' updates
and the committed parameters live in host memory. The codec and the
commit run leaf by leaf on the device.

``fault='half_batch'`` plants a fault the correctness check must catch:
half of the batch left out of the mean (half the workers, or half of the
positions with one worker).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["run_rounds", "leaf_norms", "FAULTS"]

FAULTS = (None, "half_batch")


@jax.jit
def _codec(u, r):
    e = u + r
    amax = jnp.max(jnp.abs(e))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    dec = jnp.clip(jnp.round(e / scale), -127.0, 127.0) * scale
    return dec, e - dec


@jax.jit
def _commit(p, total, c):
    return (p.astype(jnp.float32) - c * total).astype(p.dtype)


@jax.jit
def _scaled_norm(x, c):
    return c * jnp.linalg.norm(x.ravel())


@jax.jit
def _diff_norm(a, b):
    return jnp.linalg.norm((a.astype(jnp.float32) - b.astype(jnp.float32)).ravel())


def leaf_norms(after, before) -> list[float]:
    """Per leaf, ‖after − before‖ in float32, on the first device."""
    dev = jax.devices()[0]
    return [float(_diff_norm(jax.device_put(a, dev), jax.device_put(b, dev)))
            for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(before))]


def run_rounds(loss_fn, params0, batches, taus, *, local_lr: float, global_lr: float,
               fault=None, on_round=None):
    """Run ``len(batches)`` rounds from ``params0`` (a pytree of host
    arrays in the storage dtype). ``batches[r]`` is (τ_max, n_workers × b,
    seq) int32, worker i owning rows [i·b, (i+1)·b); ``taus[r][i]`` its
    live steps. ``loss_fn(params, tokens)`` may take ``positions``.

    Returns the per-round losses (mean over workers of the mean over
    their live steps). ``on_round(r, params, applied)`` sees the host
    parameters after each round, in the storage dtype, and per leaf the
    norm of the update the commit applied to them, ‖c · Σ decoded‖,
    before they are stored."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    leaves, treedef = jax.tree.flatten(params0)
    P = [np.asarray(x) for x in leaves]
    n = len(taus[0])
    dev = jax.devices()[0]
    resid = [[np.zeros(x.shape, np.float32) for x in P] for _ in range(n)]
    kw = {}
    if fault == "half_batch" and n == 1:
        kw["positions"] = (batches[0].shape[-1] - 1) // 2

    def step(p, u, tokens):
        loss, g = jax.value_and_grad(lambda q: loss_fn(q, tokens, **kw))(p)
        p = jax.tree.map(lambda a, b: (a.astype(jnp.float32) - local_lr * b).astype(a.dtype),
                         p, g)
        return loss, p, jax.tree.map(lambda a, b: a + local_lr * b, u, g)

    step = jax.jit(step, donate_argnums=(0, 1))
    kept = list(range(n // 2 if fault == "half_batch" and n > 1 else n))
    c = jnp.float32(global_lr / len(kept))
    losses = []
    for r, (toks, tau_r) in enumerate(zip(batches, taus)):
        b = toks.shape[1] // n
        total = [None] * len(P)
        worker_losses = []
        for i in range(n):
            p = jax.tree.unflatten(treedef, [jax.device_put(x, dev) for x in P])
            u = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p)
            ls = []
            for j in range(int(tau_r[i])):
                loss, p, u = step(p, u, jnp.asarray(toks[j, i * b:(i + 1) * b]))
                ls.append(loss)
            worker_losses.append(float(np.mean([float(x) for x in ls])))
            del p
            u_leaves = jax.tree.leaves(u)
            del u
            for k in range(len(P)):
                dec, res = _codec(u_leaves[k], jax.device_put(resid[i][k], dev))
                u_leaves[k] = None
                resid[i][k] = np.asarray(res)
                if i not in kept:
                    continue
                if len(kept) == 1:
                    total[k] = dec
                elif total[k] is None:
                    total[k] = np.array(dec)
                else:
                    total[k] += np.asarray(dec)
        applied = []
        for k in range(len(P)):
            t_k = jax.device_put(total[k], dev)
            applied.append(float(_scaled_norm(t_k, c)))
            P[k] = np.asarray(_commit(jax.device_put(P[k], dev), t_k, c))
            total[k] = t_k = None
        losses.append(float(np.mean(worker_losses)))
        if on_round is not None:
            on_round(r, jax.tree.unflatten(treedef, P), applied)
    return losses
