"""Cluster ADSP commit layer: semantics on a 1-device mesh + equivalences.

(The multi-device sharding path is exercised by the dry-run and by
tests/test_dryrun_smoke.py which runs in a subprocess with fake devices.)
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ps import (
    AdspState,
    CommitConfig,
    UpdateRules,
    effective_momentum,
    make_train_step,
)

SEED_RULES = UpdateRules(local="sgd", commit="momentum_delta", backend="reference")


def make_adsp_step(loss_fn, cfg, mesh, batch_spec=None):
    """The seed's worker-axes ADSP step via the unified factory."""
    return make_train_step(loss_fn, cfg, SEED_RULES, mesh=mesh,
                           batch_spec=batch_spec)


def make_accum_step(loss_fn, cfg):
    """The seed's τ-step accumulation (no worker axis) via the factory."""
    return make_train_step(loss_fn, dataclasses.replace(cfg, worker_axes=()),
                           SEED_RULES)


def quad_loss(params, batch):
    x, y = batch
    pred = x @ params["w"]
    return jnp.mean((pred - y) ** 2)


@pytest.fixture()
def problem():
    rng = np.random.default_rng(0)
    w_true = rng.normal(size=(4, 1)).astype(np.float32)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    y = x @ w_true
    params = {"w": jnp.zeros((4, 1), jnp.float32)}
    return params, (jnp.asarray(x), jnp.asarray(y))


def _mesh1():
    return jax.make_mesh((1,), ("data",))


def test_adsp_step_tau1_equals_sgd(problem):
    """One worker, τ=1, no momentum ⇒ exactly W − η_g·η_l·∇ℓ."""
    params, (x, y) = problem
    cfg = CommitConfig(tau=1, local_lr=0.1, global_lr=1.0, worker_axes=("data",))
    mesh = _mesh1()
    with jax.set_mesh(mesh):
        step = make_adsp_step(quad_loss, cfg, mesh, batch_spec=jax.sharding.PartitionSpec(None, "data"))
        state = AdspState.create(params)
        mb = (x[None], y[None])  # tau leading dim
        tau = jnp.ones((1,), jnp.int32)
        new_state, loss = step(state, mb, tau)
    _, g = jax.value_and_grad(quad_loss)(params, (x, y))
    expect = params["w"] - 0.1 * g["w"]
    np.testing.assert_allclose(np.asarray(new_state.params["w"]), np.asarray(expect), rtol=1e-6)
    assert float(loss) == pytest.approx(float(quad_loss(params, (x, y))), rel=1e-5)


def test_adsp_step_masking(problem):
    """tau_i=1 with cfg.tau=3 must ignore microsteps 2 and 3."""
    params, (x, y) = problem
    cfg = CommitConfig(tau=3, local_lr=0.1, global_lr=1.0, worker_axes=("data",))
    mesh = _mesh1()
    with jax.set_mesh(mesh):
        step = make_adsp_step(quad_loss, cfg, mesh, batch_spec=jax.sharding.PartitionSpec(None, "data"))
        mb = (jnp.stack([x, x, x]), jnp.stack([y, y, y]))
        s1, _ = step(AdspState.create(params), mb, jnp.asarray([1], jnp.int32))
        s3, _ = step(AdspState.create(params), mb, jnp.asarray([3], jnp.int32))
    _, g = jax.value_and_grad(quad_loss)(params, (x, y))
    expect1 = params["w"] - 0.1 * g["w"]
    np.testing.assert_allclose(np.asarray(s1.params["w"]), np.asarray(expect1), rtol=1e-6)
    # 3 live steps move further than 1
    assert float(jnp.linalg.norm(s3.params["w"] - params["w"])) > float(
        jnp.linalg.norm(s1.params["w"] - params["w"])
    )


def test_accum_step_matches_adsp_single_worker(problem):
    params, (x, y) = problem
    cfg = CommitConfig(tau=2, local_lr=0.05, global_lr=1.0, worker_axes=("data",))
    mesh = _mesh1()
    mb = (jnp.stack([x, x]), jnp.stack([y, y]))
    with jax.set_mesh(mesh):
        adsp = make_adsp_step(quad_loss, cfg, mesh, batch_spec=jax.sharding.PartitionSpec(None, "data"))
        s_a, loss_a = adsp(AdspState.create(params), mb, jnp.asarray([2], jnp.int32))
    accum = make_accum_step(quad_loss, cfg)
    s_b, loss_b = accum(AdspState.create(params), mb, jnp.asarray(2, jnp.int32))
    np.testing.assert_allclose(
        np.asarray(s_a.params["w"]), np.asarray(s_b.params["w"]), rtol=1e-6
    )
    assert float(loss_a) == pytest.approx(float(loss_b), rel=1e-6)


def test_adsp_step_converges(problem):
    params, (x, y) = problem
    cfg = CommitConfig(tau=4, local_lr=0.05, global_lr=1.0, worker_axes=("data",))
    mesh = _mesh1()
    with jax.set_mesh(mesh):
        step = make_adsp_step(quad_loss, cfg, mesh, batch_spec=jax.sharding.PartitionSpec(None, "data"))
        state = AdspState.create(params)
        mb = (jnp.broadcast_to(x, (4, *x.shape)), jnp.broadcast_to(y, (4, *y.shape)))
        tau = jnp.asarray([4], jnp.int32)
        losses = []
        for _ in range(30):
            state, loss = step(state, mb, tau)
            losses.append(float(loss))
    assert losses[-1] < 0.01 * losses[0]


def test_effective_momentum_correction():
    cfg = CommitConfig(momentum=0.9, gamma=60.0, correct_implicit_momentum=True)
    # high commit rate ⇒ little implicit momentum ⇒ explicit ≈ target
    hi = effective_momentum(cfg, speeds=[4, 4, 4], delta_c=[30, 30, 30])
    # low rate ⇒ large implicit ⇒ explicit shrinks (floor at 0)
    lo = effective_momentum(cfg, speeds=[4, 4, 4], delta_c=[1, 1, 1])
    assert hi > lo >= 0.0
    cfg2 = CommitConfig(momentum=0.9, correct_implicit_momentum=False)
    assert effective_momentum(cfg2, [1], [1]) == 0.9
