"""Typed update-rule API for the ADSP data plane (DESIGN.md §9).

The paper's PS commit (Alg. 2, Eqn. 1) is optimizer-agnostic — workers
ship an accumulated parameter update U, not gradients — so the data plane
factors into two independently pluggable pieces:

  * ``LocalRule``   — the per-microstep worker optimizer (what each live
    microstep does to the worker's local params and to U);
  * ``CommitRule``  — the PS apply over the worker axes (how the
    pmean-ed U becomes the next global params).

Each (rule, backend) pair is registered here; ``backend`` is either
``"reference"`` (pure-JAX, the correctness contract) or ``"fused"``
(single-HBM-pass Pallas kernels from ``repro.kernels``, in interpret
mode off-TPU — see ``kernels.ops.default_interpret``). ``resolve_backend`` maps
the default ``"auto"`` to fused on TPU and reference elsewhere, and a
fused request for a rule with no fused implementation falls back to its
reference implementation.

Contracts (all pytree-preserving, jit/shard_map-safe, dtype-stable so
they can sit in a ``lax.scan`` carry):

  LocalRule.init(params) -> local_state            (no worker dim)
  LocalRule.update(params, u, grads, state, live)
      -> (new_params, new_u, new_state)
    ``live`` is a float32 scalar in {0.0, 1.0}; masked (live=0) steps
    must leave params, U, and state unchanged (the τ_i rate-rule mask).

  CommitRule.init(params) -> commit_state
  CommitRule.apply(params, commit_state, u, momentum)
      -> (new_params, new_commit_state)
    ``u`` is the worker-mean accumulated update (already pmean-ed and
    cast to ``commit_dtype`` by the train step); ``momentum`` is the
    explicit PS momentum (post implicit-momentum correction).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax

__all__ = [
    "LocalRule",
    "CommitRule",
    "UpdateRules",
    "register_local_rule",
    "register_commit_rule",
    "get_local_rule",
    "get_commit_rule",
    "local_rule_names",
    "commit_rule_names",
    "rule_backends",
    "resolve_backend",
]

Pytree = Any


@dataclasses.dataclass(frozen=True)
class LocalRule:
    """Per-microstep worker optimizer (see module docstring for the
    ``init``/``update`` contracts)."""

    name: str
    backend: str
    init: Callable[[Pytree], Pytree]
    update: Callable[..., tuple]


@dataclasses.dataclass(frozen=True)
class CommitRule:
    """PS apply over the worker axes (see module docstring for the
    ``init``/``apply`` contracts).

    ``is_payload`` marks codec-consuming rules (the fused decode+apply
    path, DESIGN.md §16): when set, ``apply``'s ``u`` is an *encoded*
    payload tree whose per-leaf atoms this predicate identifies (e.g.
    the int8 ``{"q", "scale"}`` dict). ``make_sharded_apply`` uses it to
    slice payload trees leaf-aligned with the params; None means ``u``
    is a dense params-shaped tree (every classic rule)."""

    name: str
    backend: str
    init: Callable[[Pytree], Pytree]
    apply: Callable[..., tuple]
    is_payload: Callable[[Any], bool] | None = None


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

_LOCAL: dict[tuple[str, str], Callable] = {}
_COMMIT: dict[tuple[str, str], Callable] = {}


def register_local_rule(name: str, backend: str = "reference"):
    """Decorator: register ``factory(ccfg, *, interpret=None, **hp) ->
    LocalRule`` under (name, backend)."""

    def deco(factory):
        _LOCAL[(name, backend)] = factory
        return factory

    return deco


def register_commit_rule(name: str, backend: str = "reference"):
    def deco(factory):
        _COMMIT[(name, backend)] = factory
        return factory

    return deco


def local_rule_names() -> tuple[str, ...]:
    return tuple(sorted({n for n, _ in _LOCAL}))


def commit_rule_names() -> tuple[str, ...]:
    return tuple(sorted({n for n, _ in _COMMIT}))


def rule_backends(kind: str, name: str) -> tuple[str, ...]:
    table = _LOCAL if kind == "local" else _COMMIT
    return tuple(sorted(b for n, b in table if n == name))


def resolve_backend(requested: str | None = None) -> str:
    """``"auto"``/None → ``"fused"`` when a TPU backend is present (the
    kernels compile natively there), ``"reference"`` elsewhere — CPU
    interpret-mode Pallas is a validation path, not a fast path, so it is
    opt-in via an explicit ``backend="fused"``."""
    if requested in ("reference", "fused"):
        return requested
    if requested not in (None, "auto"):
        raise ValueError(
            f"unknown rule backend {requested!r} (want 'reference', 'fused', 'auto')"
        )
    return "fused" if jax.default_backend() == "tpu" else "reference"


def _lookup(table: dict, kind: str, name: str, backend: str | None) -> Callable:
    want = resolve_backend(backend)
    factory = table.get((name, want))
    if factory is None and want == "fused":
        factory = table.get((name, "reference"))  # no fused impl: fall back
    if factory is None:
        known = sorted({n for n, _ in table})
        raise KeyError(f"no {kind} rule {name!r}; registered: {known}")
    return factory


def get_local_rule(name, ccfg, *, backend: str | None = None,
                   interpret: bool | None = None, **hp) -> LocalRule:
    """Instantiate a registered local rule. ``name`` may already be a
    LocalRule (passed through). Hyperparameters default from ``ccfg``
    (e.g. sgd's lr is ``ccfg.local_lr``); ``hp`` overrides."""
    if isinstance(name, LocalRule):
        return name
    return _lookup(_LOCAL, "local", name, backend)(ccfg, interpret=interpret, **hp)


def get_commit_rule(name, ccfg, *, backend: str | None = None,
                    interpret: bool | None = None, **hp) -> CommitRule:
    if isinstance(name, CommitRule):
        return name
    return _lookup(_COMMIT, "commit", name, backend)(ccfg, interpret=interpret, **hp)


# --------------------------------------------------------------------------
# the bundle make_train_step consumes
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class UpdateRules:
    """Names (or instances) of the local/commit rules plus backend policy.

    backend: 'reference' | 'fused' | None/'auto' (fused on TPU only).
    interpret: Pallas interpret override for fused kernels; None defers
      to the backend probe (kernels.ops).
    local_hp / commit_hp: extra hyperparameters forwarded to the rule
      factories (e.g. {'lr': 1e-3} for adamw).
    """

    local: str | LocalRule = "sgd"
    commit: str | CommitRule = "momentum_delta"
    backend: str | None = None
    interpret: bool | None = None
    local_hp: dict = dataclasses.field(default_factory=dict)
    commit_hp: dict = dataclasses.field(default_factory=dict)

    def resolve(self, ccfg) -> tuple[LocalRule, CommitRule]:
        local = get_local_rule(self.local, ccfg, backend=self.backend,
                               interpret=self.interpret, **self.local_hp)
        commit = get_commit_rule(self.commit, ccfg, backend=self.backend,
                                 interpret=self.interpret, **self.commit_hp)
        return local, commit


def mask_tree(live, new: Pytree, old: Pytree) -> Pytree:
    """Select ``new`` where the microstep is live, else keep ``old``,
    leaf-wise and dtype-preserving (works for int leaves like step
    counters). ``live`` is the scan's float32 {0,1} scalar."""
    on = live > 0
    return jax.tree.map(lambda n, o: jax.numpy.where(on, n, o), new, old)
