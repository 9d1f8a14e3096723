"""Chip smoke: drive both device paths once on a TPU, at published widths.

    python chip_smoke.py               # one chip: train + serve phases
    python chip_smoke.py --four-chips  # four chips: ADSP across workers only

Phases (each failing phase makes the exit code non-zero):

  * device — the first JAX device must be a TPU; Pallas must resolve to
    native (non-interpret) kernels.
  * train — granite-3-8b at every published width, cut to ``LAYERS``
    layers (the depth cut one 16 GB chip forces), through
    ``repro.launch.train.make_trainer`` and ``MeshBackend.train``: seq
    2048, batch 1, τ=2, 3 commit rounds on the fast path (flash
    attention, fused sgd, int8 codec, fused decode+apply commit).
    Checks the fast path's Pallas calls in the compiled step and finite
    losses, and compares the committed params after one round with the
    reference chain (reference rules and codec, no fused commit) from
    the same start on the same data.
  * serve — rwkv6-3b whole (32 layers) through ``repro.launch.serve``'s
    engine on an 8-request Poisson trace over 4 slots; every request must
    be answered. One request's tokens must equal the same request served
    alone by the same engine programs, and each must be the argmax, up to
    ``SERVE_LOGIT_TOL``, of the solo-decode oracle (the full forward
    ``lm_prefill`` + ``lm_decode_step``) fed the served prefix, and the
    engine's own prefill program, replayed, must give its first token.
  * four chips (``--four-chips`` only) — the same granite cut as 4 ADSP
    workers on a data=4 mesh with unequal speeds, so the per-worker τ_i
    differ. Checks the per-worker state and the params are spread over
    the 4 devices, and compares one round with a one-device reference:
    each worker's τ_i local steps in turn, then the mean update applied
    by the commit rule. The fused decode+apply commit needs a single
    worker, so this phase runs the chain commit.

The run stays in this one process: a chip belongs to one process at a
time. The last line of standard output is one JSON object, printed only
when every phase passed. Times and device utilization come from the
benchmark (``chipbench/run.py``), not from here.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
# granite-3-8b layers that fit one v5e with the ADSP train state: the
# TPU compiler refuses 4 (17.98 GiB of HBM for 15.75)
LAYERS = 3
TRAIN = dict(arch="granite-3-8b", seq=2048, batch=1, tau=2, rounds=3,
             local_lr=0.02, global_lr=1.0, seed=0)
FOUR = dict(batch=4, tau=4, speeds=(0.5, 0.375, 0.25, 0.125))  # τ_i 4:3:2:1
# flash attention, fused sgd, int8 encode with error feedback, fused
# int8 decode + commit apply
FAST_PATH_KERNELS = {"flash_attention", "accumulate_tree", "quantize_int8_ef",
                     "int8_decode_apply"}
# A served token may trail the solo-decode oracle's best logit by this
# much. Both are bf16 programs of rwkv6-3b whose first-token logits
# were measured on a v5e to differ by up to 0.36 (each up to 0.55 from
# a float32 reference); a token one ranks first trails the other's
# best by at most twice their elementwise gap.
SERVE_LOGIT_TOL = 0.75
SERVE = ["--arch", "rwkv6-3b", "--trace", "poisson", "--requests", "8",
         "--slots", "4", "--seed", "0"]


class PhaseFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseFailed(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# parity: committed params against a reference from the same start
# ---------------------------------------------------------------------------

def compare_params(got, want, start) -> dict:
    """Elementwise |got − want| ≤ tol per leaf, with
    tol = 2⁻⁷·|want| + 4·max|want − start| / 127.

    Why: params are bf16, so each side rounds to within half a bf16
    spacing of the exact value, and one spacing is at most 2⁻⁷ of the
    magnitude. The update crosses the int8 codec, whose step per leaf is
    max|e|/127 — bounded by max|want − start|/127 with global lr 1. Two
    paths may land up to four steps apart: one for the quantiser's
    rounding, a fraction of one for the scale (the fused sgd rounds the
    local lr to bf16, +0.1%), and up to two for U itself, which the fused
    sgd keeps in bf16 — each of τ=2 microsteps rounds U by up to 2⁻⁹ of
    its magnitude, about half a step for the largest elements, on either
    path. Also requires the round to have moved the params at all."""
    worst, moved = 0.0, 0.0
    for g, w, s in zip(*map(jax.tree.leaves, (got, want, start))):
        g, w, s = (np.asarray(x, np.float32) for x in (g, w, s))
        step = float(np.max(np.abs(w - s)))
        moved = max(moved, step)
        tol = 2.0 ** -7 * np.abs(w) + 4.0 * step / 127.0
        worst = max(worst, float(np.max(np.abs(g - w) / np.maximum(tol, 1e-30))))
    return {"worst_err_over_tol": worst, "max_update": moved,
            "ok": worst <= 1.0 and moved > 0.0}


def pallas_calls(hlo: str) -> dict:
    """Pallas kernels in a compiled TPU program: instruction name (the
    kernel's wrapper) → count of ``tpu_custom_call`` sites."""
    out: dict = {}
    for m in re.finditer(r"%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*"
                         r'custom_call_target="tpu_custom_call"', hlo):
        out[m.group(1)] = out.get(m.group(1), 0) + 1
    return out


def release() -> None:
    """Free the device buffers of trainers the caller has dropped: a
    backend and its engine point at each other, so only the cycle
    collector frees them."""
    gc.collect()


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def device_phase(n_chips: int) -> dict:
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache

    devs = jax.devices()
    dev = devs[0]
    check(dev.platform == "tpu", f"no TPU: JAX found {dev.platform!r}")
    check(len(devs) >= n_chips, f"{n_chips} chips asked for, {len(devs)} found")
    cache = enable_compile_cache()
    interp = ops.default_interpret()
    log(f"[device] kind={dev.device_kind!r} count={len(devs)} "
        f"jax={jax.__version__} cache={cache} pallas_interpret={interp}")
    check(interp is False, "Pallas resolved to interpret mode on the TPU")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": n_chips}


def _granite_cut():
    from repro.configs import get_config
    from repro.launch.train import cut_layers

    return cut_layers(get_config(TRAIN["arch"]), LAYERS)


def _trainer(cfg, mesh, *, backend, fused_commit, batch, tau, speeds=None):
    from repro.launch.train import make_trainer
    from repro.ps import UpdateRules
    from repro.transport import get_codec

    return make_trainer(
        cfg, mesh, tau=tau, seq=TRAIN["seq"], batch=batch,
        local_lr=TRAIN["local_lr"], global_lr=TRAIN["global_lr"],
        seed=TRAIN["seed"], speeds=speeds,
        update_rules=UpdateRules(backend=backend),
        codec=get_codec("int8", backend=backend),
        fused_commit=fused_commit, attn_impl="flash")


def _one_round(backend, mesh):
    """One commit round of a fresh backend; returns the committed params
    on the host."""
    with jax.set_mesh(mesh):
        backend.train(1)
    return jax.device_get(backend.state.params)


def train_phase(cfg, mesh) -> None:
    from repro.launch.train import unmet_requests

    t = TRAIN
    log(f"[train] {cfg.name}: layers {cfg.num_layers} (reduced: depth only, "
        f"every width as published), d_model {cfg.d_model}, heads "
        f"{cfg.num_heads}/{cfg.num_kv_heads}x{cfg.head_dim_}, d_ff {cfg.d_ff}, "
        f"vocab {cfg.padded_vocab}, params {cfg.total_params() / 1e9:.3f} B; "
        f"seq {t['seq']} batch {t['batch']} tau {t['tau']} rounds {t['rounds']}")
    backend, engine, policy = _trainer(cfg, mesh, backend="fused",
                                       fused_commit=True, batch=t["batch"],
                                       tau=t["tau"])
    unmet = unmet_requests(backend, rule_backend="fused",
                           codec_backend="fused", fused_commit=True)
    check(not unmet, "; ".join(unmet))
    start = jax.device_get(backend.state.params)

    with jax.set_mesh(mesh):
        mbs = backend.task.make_microbatches(0, t["tau"], 1)
        tau_arr = jnp.asarray(backend.tau_per_worker(), jnp.int32)
        compiled = backend.step_fn.lower(backend.state, mbs, tau_arr).compile()
    kernels = pallas_calls(compiled.as_text())
    mem = compiled.memory_analysis()
    log(f"[train] tpu_custom_call x{sum(kernels.values())}: {kernels}")
    log(f"[train] memory_analysis: arguments {mem.argument_size_in_bytes / 2**30:.2f} "
        f"GiB, temp {mem.temp_size_in_bytes / 2**30:.2f} GiB, aliased "
        f"{mem.alias_size_in_bytes / 2**30:.2f} GiB (the compiler's own HBM "
        f"check admitted the step; temp here overstates what it needs)")
    missing = FAST_PATH_KERNELS - set(kernels)
    check(not missing, f"fast-path kernels missing from the compiled step: "
          f"{sorted(missing)}")

    after_first = {}

    def on_round(rnd, loss):
        if rnd == 1:
            after_first["params"] = jax.device_get(backend.state.params)

    with jax.set_mesh(mesh):
        backend.train(t["rounds"], check_period=policy.gamma, on_round=on_round)
    losses = [l for _, l in backend.losses]
    log(f"[train] losses {[round(l, 4) for l in losses]}")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    del backend, engine, policy, compiled
    release()

    ref, _, _ = _trainer(cfg, mesh, backend="reference", fused_commit=False,
                         batch=t["batch"], tau=t["tau"])
    check(not ref.fused_commit and ref.codec.backend == "reference",
          "the reference trainer did not resolve to the reference chain")
    want = _one_round(ref, mesh)
    del ref
    release()
    par = compare_params(after_first["params"], want, start)
    log(f"[train] parity fused vs reference chain after round 1: {par}")
    check(par["ok"], "fused commit path disagrees with the reference chain")


def oracle_logits(cfg, params, prompt, tokens, capacity: int) -> list:
    """The solo-decode oracle (``repro.serve.solo_decode``: the full
    forward ``lm_prefill``, then ``lm_decode_step`` at batch 1 and the
    pool's cache capacity), fed ``tokens`` instead of its own argmax.
    Returns float32 logits on the host before each token."""
    from repro.models import lm

    plen = prompt.shape[1]
    prefill = jax.jit(lambda p, t: lm.lm_prefill(
        cfg, p, {"tokens": t}, reserve=capacity - plen))
    decode = jax.jit(lambda p, t, c: lm.lm_decode_step(cfg, p, {"tokens": t}, c))
    logits, caches = prefill(params, jnp.asarray(prompt, jnp.int32))
    out = [np.asarray(logits[0], np.float32)]
    for tok in tokens[:-1]:
        logits, caches = decode(params, jnp.asarray([[tok]], jnp.int32), caches)
        out.append(np.asarray(logits[0, 0], np.float32))
    return out


def serve_phase(serve_argv) -> None:
    from repro.launch.serve import build_parser, run_engine
    from repro.serve import ServeEngine

    args = build_parser().parse_args(serve_argv)
    out = run_engine(args)
    report, engine, trace = out["report"], out["engines"][0], out["trace"]
    cfg = engine.cfg
    log(f"[serve] {cfg.name}: layers {cfg.num_layers} (whole), d_model "
        f"{cfg.d_model}, params {cfg.total_params() / 1e9:.3f} B, dtype "
        f"{cfg.dtype}; {report.decode_steps} decode steps")
    served = {r.req for r in report.records}
    check(served == {r.rid for r in trace}
          and all(report.tokens_by_rid[r.rid] for r in trace),
          f"answered {sorted(served)} of {[r.rid for r in trace]}")
    req = max(trace, key=lambda r: r.max_new)
    pooled = report.tokens_by_rid[req.rid]
    prompt = engine.prompt_tokens(req)

    # the same request served alone: same engine programs (slot width,
    # cache capacity, prefill bucket), no other occupant, no eviction or
    # backfill around it — what continuous batching must not change
    alone_cfg = dataclasses.replace(engine.serve_cfg,
                                    capacity=engine.pool.capacity)
    alone = ServeEngine(cfg, engine.params, alone_cfg, [req]).run()
    check(alone.tokens_by_rid[req.rid] == pooled,
          f"pooled {pooled} != served alone {alone.tokens_by_rid[req.rid]}")

    # each served token against the solo-decode oracle, fed the served
    # prefix: it must be the oracle's argmax up to SERVE_LOGIT_TOL
    v = cfg.vocab_size
    oracle = [l[:v] for l in oracle_logits(cfg, engine.params, prompt, pooled,
                                           engine.pool.capacity)]
    gaps = [float(l.max() - l[t]) for l, t in zip(oracle, pooled)]
    argmax = [int(np.argmax(l)) for l in oracle]
    same = [a == t for a, t in zip(argmax, pooled)]
    prefix = same.index(False) if False in same else len(same)
    log(f"[serve] request {req.rid}: prompt {req.prompt_len}, {req.max_new} "
        f"tokens; pooled {pooled} (the same served alone); solo-decode "
        f"oracle argmax {argmax}: equal "
        f"at {sum(same)}/{len(same)} steps, so its own greedy stream matches "
        f"the pooled one for {prefix} tokens; oracle logit gap of the "
        f"served token max {max(gaps):.4f} (tol {SERVE_LOGIT_TOL}; oracle "
        f"logit std {float(np.mean([l.std() for l in oracle])):.3f})")
    check(max(gaps) <= SERVE_LOGIT_TOL,
          f"a served token trails the oracle's best by {max(gaps):.4f} logits")

    # the engine's own prefill program for the request's bucket, replayed
    first, _ = engine._prefill(req)
    check(first == pooled[0], f"prefill replay gave {first}, served {pooled[0]}")


def one_slot_per_device(tree, n: int):
    """Whether every leaf is split over ``n`` devices one leading slot
    each; None for a tree with no leaves."""
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return None
    return all(len(x.sharding.device_set) == n
               and x.sharding.shard_shape(x.shape)[0] == 1 for x in leaves)


def four_chip_phase(cfg, mesh) -> None:
    from repro.ps import CommitConfig, make_local_update

    f = FOUR
    backend, engine, policy = _trainer(cfg, mesh, backend="auto",
                                       fused_commit=False, batch=f["batch"],
                                       tau=f["tau"], speeds=list(f["speeds"]))
    n = len(backend.workers)
    log(f"[four] {cfg.name} layers {cfg.num_layers}: {n} workers on mesh "
        f"{dict(zip(mesh.axis_names, mesh.devices.shape))}, speeds "
        f"{list(f['speeds'])}, tau {f['tau']}, batch {f['batch']}; commit: "
        f"chain (the fused decode+apply commit needs one worker), rules "
        f"{[r.backend for r in backend.rules]}, codec "
        f"{backend.codec.name}[{backend.codec.backend}]")
    check(n == 4, f"{n} workers")
    start = jax.device_get(backend.state.params)
    mbs = backend.task.make_microbatches(0, f["tau"], n)
    local_rule, commit_rule = backend.rules
    codec, loss_fn = backend.codec, backend.task.loss_fn

    with jax.set_mesh(mesh):
        backend.train(1)
        jax.block_until_ready(backend.state)
    taus = [w.steps for w in backend.workers]
    st = backend.state
    spread = {"local_state": one_slot_per_device(st.local_state, n),
              "transport_state": one_slot_per_device(st.transport_state, n)}
    param_devs = {len(x.sharding.device_set) for x in jax.tree.leaves(st.params)}
    log(f"[four] round 1: tau_i {taus}; "
        f"loss {backend.losses[-1][1]:.4f}; one slot per device "
        f"{spread} (None: no leaves, sgd is stateless); param device_set "
        f"sizes {param_devs}")
    check(len(set(taus)) > 1, f"tau_i do not differ: {taus}")
    check(param_devs == {n}, f"params not spread over {n} devices: {param_devs}")
    check(spread["transport_state"] is True and spread["local_state"] is not False,
          f"per-worker state not one slot per device: {spread}")
    got = jax.device_get(st.params)
    # every name bound to the 4-device state goes, or device 0 keeps its
    # slot of it (one f32 copy of the params) under the reference's round
    del backend, engine, policy, st
    release()

    # one-device reference: each worker's τ_i local steps in turn, its
    # update through the codec with a fresh residual, the worker mean
    # applied by the commit rule — the same round without shard_map
    ccfg = CommitConfig(tau=f["tau"], local_lr=TRAIN["local_lr"],
                        global_lr=TRAIN["global_lr"], worker_axes=())
    run = make_local_update(loss_fn, ccfg, local_rule)

    def add_worker(total, params, mb, tau_i):
        u, _, _ = run(params, local_rule.init(params), mb, tau_i)
        enc, _ = codec.encode(u, codec.init(u))
        return jax.tree.map(lambda t, d: t + d.astype(jnp.float32), total,
                            codec.decode(enc, u))

    add_worker = jax.jit(add_worker, donate_argnums=0)
    params = jax.device_put(start, jax.devices()[0])
    total = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    per = f["batch"] // n
    for i, tau_i in enumerate(taus):
        mb = jax.tree.map(lambda x: x[:, i * per:(i + 1) * per], mbs)
        total = add_worker(total, params, mb, jnp.asarray(tau_i, jnp.int32))
    mean = jax.tree.map(lambda x: x / n, total)
    del total
    want, _ = commit_rule.apply(params, commit_rule.init(params), mean, 0.0)
    par = compare_params(got, jax.device_get(want), start)
    log(f"[four] parity 4-device round vs one-device reference: {par}")
    check(par["ok"], "the 4-device round disagrees with the one-device reference")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the four-chip ADSP phase and its reference")
    args = p.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro sources under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    n_chips = 4 if args.four_chips else 1
    try:
        device = device_phase(n_chips)
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((n_chips,), ("data",),
                         devices=jax.devices()[:n_chips])
        if args.four_chips:
            four_chip_phase(_granite_cut(), mesh)
        else:
            train_phase(_granite_cut(), mesh)
            serve_phase(SERVE)
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
