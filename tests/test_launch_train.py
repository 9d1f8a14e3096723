"""The training entry point: GSPMD meshes, the depth cut, weights in the
model dtype, the compile cache, and errors instead of silent fallbacks."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, PartitionSpec as P

from repro.configs import get_config, get_smoke
from repro.launch import train as launch_train
from repro.launch.compile_cache import CHECKOUT, enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.models.layers import annotate


def _main(capsys, *extra):
    launch_train.main(["--arch", "granite-3-8b", "--smoke", "--steps", "2",
                       "--seq", "32", "--batch", "1", "--tau", "2", *extra])
    return capsys.readouterr().out


def test_make_mesh_axes_are_auto():
    mesh = make_mesh((1,), ("data",))
    assert tuple(mesh.axis_types) == (AxisType.Auto,)


def test_launch_train_flash_on_launcher_mesh(capsys):
    """Flash attention trains on the launcher's own mesh; on Explicit mesh
    axes it failed in a dynamic_update_slice sharding check."""
    out = _main(capsys, "--attn-impl", "flash", "--layers", "1")
    assert "attn=pallas" in out
    assert "# reduced: layers 1 of 2, every width as published" in out
    losses = [float(l.split("loss")[1].split()[0])
              for l in out.splitlines() if l.startswith("step")]
    assert losses and all(np.isfinite(losses))


def test_flash_trains_on_auto_data_model_mesh():
    """The 1×1 data×model mesh that failed with a ShardingTypeError under
    Explicit axes trains once its axes are GSPMD (CPU: interpret mode)."""
    mesh = make_mesh((1, 1), ("data", "model"))
    backend, _, _ = launch_train.make_trainer(
        get_smoke("granite-3-8b"), mesh, tau=2, seq=32, batch=1,
        local_lr=0.02, global_lr=1.0, attn_impl="flash")
    with jax.set_mesh(mesh):
        backend.train(1)
    assert np.isfinite(backend.losses[-1][1])


@pytest.mark.parametrize("extra,needle", [
    (("--rule-backend", "fused", "--local-rule", "adamw"), "--rule-backend fused"),
    (("--codec-backend", "fused"), "--codec-backend fused"),
    (("--fused-commit",), "--fused-commit did not take effect"),
])
def test_launch_train_rejects_unmet_request(capsys, extra, needle):
    """An explicit fused request that resolved to a fallback is an error."""
    with pytest.raises(SystemExit) as e:
        _main(capsys, *extra)
    assert e.value.code == 2
    assert needle in capsys.readouterr().err


def test_make_trainer_rejects_batch_not_split_over_workers():
    two_workers = types.SimpleNamespace(axis_names=("data",),
                                        devices=np.empty((2,), object))
    with pytest.raises(ValueError, match="batch 3 does not split over 2"):
        launch_train.make_trainer(get_smoke("granite-3-8b"), two_workers,
                                  tau=2, seq=16, batch=3, local_lr=0.1,
                                  global_lr=1.0)


def test_cut_layers_keeps_widths():
    full = get_config("granite-3-8b")
    cut = launch_train.cut_layers(full, 2)
    assert cut.num_layers == 2
    assert dataclasses.replace(cut, num_layers=full.num_layers) == full
    for bad in (0, full.num_layers + 1):
        with pytest.raises(ValueError, match="--layers"):
            launch_train.cut_layers(full, bad)
    rg = get_config("recurrentgemma-9b")  # period 3: a cut keeps one whole
    with pytest.raises(ValueError, match="whole period"):
        launch_train.cut_layers(rg, 2)


def test_lm_init_cast_matches_cast_of_f32_init():
    cfg = dataclasses.replace(get_smoke("granite-3-8b"), dtype="bfloat16")
    got = lm.lm_init_cast(jax.random.PRNGKey(0), cfg)
    want = lm.lm_init(jax.random.PRNGKey(0), cfg)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w.astype(jnp.bfloat16), np.float32))


def test_annotate_needs_an_ambient_mesh_and_raises_under_one():
    x = jnp.ones((2, 4))
    rules = {"mlp": "model"}
    # no ambient mesh: nothing to constrain against
    assert annotate(x, ("batch", "mlp"), rules) is x
    # GSPMD axes: the constraint lands in the program
    with jax.set_mesh(make_mesh((1,), ("model",))):
        hlo = jax.jit(lambda a: annotate(a, ("batch", "mlp"), rules)).lower(x).as_text()
    assert "sharding" in hlo
    # a constraint the mesh rejects raises instead of being dropped
    explicit = jax.make_mesh((1,), ("model",), axis_types=(AxisType.Explicit,))
    with jax.set_mesh(explicit):
        y = jax.device_put(x, jax.sharding.NamedSharding(explicit, P()))
        with pytest.raises(Exception, match="sharding"):
            jax.jit(lambda a: annotate(a * 2, ("batch", "mlp"), rules))(y)


def test_compile_cache_dir(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == was  # nothing set in code
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(CHECKOUT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT / ".jax_cache")
        assert (CHECKOUT / "chip_smoke.py").exists()  # the checkout's root
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
