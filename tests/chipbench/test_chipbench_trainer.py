"""The training driver builds its trainer as ``repro.launch.train.make_trainer``
does (``make_trainer`` takes no weights and no token stream, so the
driver repeats its construction): the same task, the same backend
arguments and the same policy, so that a change to ``make_trainer``
cannot leave the timed path behind unseen."""

import dataclasses
import inspect

import numpy as np

from chipbench.drivers import adsp_train

CELL = "granite3-8b-l3.adsp-1w"


def _spy(monkeypatch):
    """Record the arguments of every ``build_mesh_task`` and
    ``MeshBackend`` construction, defaults filled in."""
    from repro.cluster.mesh_backend import MeshBackend
    from repro.launch import train

    calls = {"task": [], "backend": []}
    build, init = train.build_mesh_task, MeshBackend.__init__

    def build_spy(*a, **kw):
        bound = inspect.signature(build).bind(*a, **kw)
        bound.apply_defaults()
        calls["task"].append(dict(bound.arguments))
        return build(*a, **kw)

    def init_spy(self, *a, **kw):
        bound = inspect.signature(init).bind(self, *a, **kw)
        bound.apply_defaults()
        calls["backend"].append(dict(bound.arguments))
        init(self, *a, **kw)

    monkeypatch.setattr(train, "build_mesh_task", build_spy)
    monkeypatch.setattr(MeshBackend, "__init__", init_spy)
    return calls


def _settings(backend_args: dict) -> dict:
    out = {k: v for k, v in backend_args.items() if k not in ("self", "task", "mesh", "codec")}
    codec = backend_args["codec"]
    out["codec"] = (codec.name, codec.backend)
    return out


def test_driver_trainer_matches_make_trainer(tiny_cell, monkeypatch):
    import jax
    from repro.launch.mesh import make_mesh
    from repro.launch.train import make_trainer
    from repro.ps import UpdateRules
    from repro.transport import get_codec

    from chipbench import harness, traffic_gen

    ctx = tiny_cell(CELL)
    t = ctx.traffic
    cfg = harness.model_config(ctx.model)
    mesh = make_mesh((t["workers"],), ("data",), devices=jax.devices()[: t["workers"]])
    params = ctx.reference().init_params(traffic_gen.jax_key(ctx.seed), ctx.model)

    calls = _spy(monkeypatch)
    ours = adsp_train._trainer(cfg, mesh, params, t, ctx.seed)
    theirs = make_trainer(
        cfg, mesh, tau=t["tau"], seq=t["seq"], batch=t["workers"] * t["batch_per_worker"],
        local_lr=t["local_lr"], global_lr=t["global_lr"], speeds=t["speeds"],
        update_rules=UpdateRules(backend=t["backend"]),
        codec=get_codec(t["codec"], backend=t["backend"]), fused_commit=t["fused_commit"],
        attn_impl=t["attn_impl"])

    task_ours, task_theirs = calls["task"]
    task_ours.pop("seed"), task_theirs.pop("seed")  # the driver feeds its own tokens
    assert task_ours == task_theirs
    a, b = (_settings(x) for x in calls["backend"])
    assert a == b and a["tau"] == t["tau"] and a["fused_commit"] is True
    assert dataclasses.asdict(ours[2]) == dataclasses.asdict(theirs[2])
    resolved = [[(r.name, r.backend) for r in x[0].rules] + [x[0].fused_commit]
                for x in (ours, theirs)]
    assert resolved[0] == resolved[1]
    # the driver's backend holds the weights it was given, in the model's dtype
    for x, y in zip(jax.tree.leaves(ours[0].state.params), jax.tree.leaves(params)):
        assert x.dtype == y.dtype and np.array_equal(np.asarray(x), np.asarray(y))
