"""The correctness check of each cell, at a CPU test's size, with the
cell's own limits (``chipbench/cells/<cell>.json``): a sound run is
correct, and a run whose timed path is broken underneath is not, once
for each fault the cell can have.

The control (the plain reference in the program's place, computed in
fp8) sets the limits on the chip, at the cell's own size, where it reads
4 to 7 times the program's largest reading on the number that catches
it; at a test's size its readings may fall under those limits, so its
test checks that separation: the control reads at least ``SEPARATION``
times what the program reads there."""

import functools

import pytest

from chipbench import harness

TRAIN = "granite3-8b-l3.adsp-1w"
SERVE = "rwkv6-3b.chat-poisson"
SEPARATION = 3.0  # the least ratio of control to program a limit may sit between


def _correct(ctx, readings) -> bool:
    return harness.judge(readings, ctx.limits)[0]


def test_training_sound_run_is_correct(tiny_cell):
    ctx = tiny_cell(TRAIN)
    out = ctx.driver().run(ctx)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert _correct(ctx, out["readings"]), out["readings"]


def test_training_tied_head_sound_run_is_correct(tiny_cell):
    """With the head tied to the embedding, as granite-3.0 publishes it,
    the program and the reference agree on the CPU (on the chip they do
    not yet: PERF.md, Open questions)."""
    ctx = tiny_cell(TRAIN)
    ctx.config["model"]["tie_embeddings"] = True
    out = ctx.driver().run(ctx)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert _correct(ctx, out["readings"]), out["readings"]


def test_training_control_separates_from_the_program(tiny_cell):
    ctx = tiny_cell(TRAIN)
    d = ctx.driver()
    st = d.checked_setup(ctx)
    d.free_program(st)
    batches = d.checked_batches(ctx, st)
    ref = d.reference_side(ctx, st["P0"], batches, st["taus"])
    program = d.compare(d.program_side(st), ref)
    control = d.compare(d.reference_side(ctx, st["P0"], batches, st["taus"], mode="fp8"), ref)
    assert control["update1_gap"] >= SEPARATION * program["update1_gap"], (program, control)


def _unchanged_state(make_train_step):
    """The step returns the state it was given."""
    def make(*a, **kw):
        step = make_train_step(*a, **kw)

        def broken(state, mbs, tau):
            _, loss = step(state, mbs, tau)
            return state, loss

        for k, v in vars(step).items():
            setattr(broken, k, v)
        return broken
    return make


def _half_batch(lm_loss):
    """The loss of the first half of each sequence: half of the batch's
    predictions left out, the mean taken over the rest."""
    def loss(cfg, params, batch, **kw):
        s = batch["tokens"].shape[1]
        return lm_loss(cfg, params, {"tokens": batch["tokens"][:, : s // 2]}, **kw)
    return loss


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_training_fault_is_not_correct(tiny_cell, monkeypatch, fault):
    from repro.cluster import mesh_backend
    from repro.models import lm

    if fault == "unchanged_state":
        monkeypatch.setattr(mesh_backend, "make_train_step",
                            _unchanged_state(mesh_backend.make_train_step))
    else:
        monkeypatch.setattr(lm, "lm_loss", _half_batch(lm.lm_loss))
    ctx = tiny_cell(TRAIN)
    out = ctx.driver().run(ctx)
    assert not _correct(ctx, out["readings"]), out["readings"]


def test_serving_sound_run_is_correct(tiny_cell):
    ctx = tiny_cell(SERVE, seconds=2.0)
    out = ctx.driver().run(ctx)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert _correct(ctx, out["readings"]), out["readings"]


def test_serving_control_separates_from_the_program(tiny_cell):
    ctx = tiny_cell(SERVE, seconds=2.0)
    d = ctx.driver()
    out = d.run(ctx, keep=True)
    kept = out["kept"]
    gap = d.reference_gaps(ctx, kept["params"], kept["samples"], kept["served"],
                           mode="fp8", control=True)
    assert kept["samples"] and gap > 0
    assert gap >= SEPARATION * out["readings"]["token_gap"], (out["readings"], gap)


def test_serving_altered_token_is_not_correct(tiny_cell, monkeypatch):
    """Each decode step's token ids altered where they are produced."""
    from repro.serve import engine as serve_engine

    decode_fn = serve_engine.ServeEngine._decode_fn

    @functools.wraps(decode_fn)
    def altered(self, params, toks, caches):
        ids, caches = decode_fn(self, params, toks, caches)
        return (ids + 1) % self.cfg.vocab_size, caches

    monkeypatch.setattr(serve_engine.ServeEngine, "_decode_fn", altered)
    ctx = tiny_cell(SERVE, seconds=2.0)
    out = ctx.driver().run(ctx)
    assert not _correct(ctx, out["readings"]), out["readings"]
