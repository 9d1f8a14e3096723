"""Shared pieces of a benchmark run: the device check against the peak
table, the compile cache, the model configuration, the correctness
limits of a cell, the traced window, and the result line.

A run is found by name: the cell in ``BENCHMARK.json`` names its
configuration (``configs/<name>.json``) and traffic mix
(``traffic/<name>.json``); the mix names its driver
(``drivers/<driver>.py``); the configuration names its plain reference
(``reference/<name>.py``); the cell's correctness limits sit in
``cells/<cell>.json``; each per-layer metric is read by
``metrics/<metric>.py``. Adding a cell adds files and edits none.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import math
import os
import pathlib
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent

__all__ = ["NoChip", "Context", "load_cell", "require_devices", "model_config",
           "judge", "traced_window", "load_module", "LayerView", "HERE", "ROOT"]


class NoChip(RuntimeError):
    """The machine does not hold what the cell asks for."""


def load_module(path: pathlib.Path, name: str | None = None):
    """Import a benchmark file by its path (names may hold dots)."""
    name = name or "chipbench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _read(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Context:
    """Everything one run of one cell needs."""

    bench: dict
    cell: dict
    config: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    peak: dict | None = None
    device: dict | None = None

    @property
    def model(self) -> dict:
        return self.config["model"]

    def reference(self):
        return importlib.import_module(f"chipbench.reference.{self.config['reference']}")

    def driver(self):
        return importlib.import_module(f"chipbench.drivers.{self.traffic['driver']}")


def load_cell(name: str, *, seed: int, seconds: float, trace: bool, t_start: float,
              bench_path: pathlib.Path | None = None) -> Context:
    bench = _read(bench_path or ROOT / "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read(ROOT / configs[cell["config"]]["file"])
    traffic = _read(HERE / "traffic" / f"{cell['traffic']}.json")
    limits_path = HERE / "cells" / f"{name}.json"
    limits = _read(limits_path)["limits"] if limits_path.exists() else {}
    return Context(bench=bench, cell=cell, config=config, traffic=traffic,
                   limits=limits, seed=seed, seconds=seconds, trace=trace,
                   t_start=t_start)


def require_devices(n_chips: int) -> tuple[dict, dict]:
    """The cell's chips and their peaks, or ``NoChip``: no TPU, fewer
    chips than asked for, or a kind missing from ``peaks.json``. Never a
    fall-back."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < n_chips:
        raise NoChip(f"the cell asks for {n_chips} chips; JAX found {len(devs)}")
    kind = devs[0].device_kind
    peaks = _read(HERE / "peaks.json")
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in peaks.json ({sorted(peaks)})")
    return peaks[kind], {"platform": devs[0].platform, "kind": kind, "count": n_chips}


def enable_cache() -> str:
    """JAX's persistent compile cache, at the checkout's fixed path (or
    ``$JAX_COMPILATION_CACHE_DIR``), for every program however quick to
    compile, so that a warm run compiles nothing."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def model_config(m: dict):
    """The program's ``ModelConfig`` from a configuration's ``model``."""
    from repro.models.config import ModelConfig

    kw = dict(m)
    kw["layer_pattern"] = tuple(kw["layer_pattern"])
    return ModelConfig(**kw)


def check_layout(params, cfg) -> None:
    """The benchmark's weights must have the program's tree and shapes."""
    import jax
    from repro.models import lm

    want = jax.eval_shape(lambda: lm.lm_init(jax.random.PRNGKey(0), cfg))
    got_s, want_s = jax.tree.structure(params), jax.tree.structure(want)
    if got_s != want_s:
        raise ValueError(f"weight layout differs from the program's:\n{got_s}\n{want_s}")
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        if a.shape != b.shape:
            raise ValueError(f"weight shape {a.shape} where the program has {b.shape}")


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """Each reading beside its limit; correct when every reading is a
    finite number within its limit. A reading with no limit fails."""
    checks, ok = {}, True
    for name, value in readings.items():
        limit = limits.get(name)
        good = (limit is not None and value is not None
                and math.isfinite(value) and value <= limit)
        ok &= good
        checks[name] = {"value": value, "limit": limit}
    return ok and bool(readings), checks


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


@contextlib.contextmanager
def traced_window(on: bool):
    """The measured window, wrapped in its host span; with ``on`` also in
    a profiler trace. Yields a dict whose ``dir`` is the trace directory
    (removed by the caller once reduced)."""
    import jax

    box = {"dir": None}
    if not on:
        with jax.profiler.TraceAnnotation("bench.window"):
            yield box
        return
    box["dir"] = tempfile.mkdtemp(prefix="chipbench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # a span per Python call would slow the host loop
    with jax.profiler.trace(box["dir"], profiler_options=opts):
        with jax.profiler.TraceAnnotation("bench.window"):
            yield box


def cleanup(path) -> None:
    if path and os.path.isdir(path):
        import shutil

        shutil.rmtree(path, ignore_errors=True)


@dataclasses.dataclass
class LayerView:
    """What a per-layer metric's reader sees: the reduced trace of the
    window, the driver's counts (``facts``), the chip's peaks, the
    chips in use and the traffic file."""

    trace: object
    facts: dict
    peak: dict
    chips: int
    traffic: dict
