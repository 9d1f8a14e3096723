"""Open-loop serving cells: ``repro.serve.ServeEngine`` driven through its
stepping API on the host's wall clock.

Set-up makes the weights, builds the engine and warms every program the
mix uses: a prefill of each prompt length (each is its own bucket) and
the pool-wide decode step, with every slot filled once. The window then
offers the mix's requests at their arrival times (each submitted once
the wall clock reaches it), runs one engine action at a time, and stamps
every token when the action that produced it returns; every action ends
by fetching its token ids to the host. After ``--seconds`` no request
arrives, and the engine drains what is due, for at most ``DRAIN_S``.

Metrics, over every request due in the window: the 95th percentile of
time to first token from the scheduled arrival (a request left
unanswered ranks above every answered one), and ``serve_tokens_per_s``,
the tokens delivered inside the window over its length. The 95th
percentile of all gaps between consecutive output tokens, pooled (a
missing token of an unfinished request ranks last), is reported beside
them (``notes``): at the cell's rate it sits on the step between two
prefill levels and reads one or the other, run to run.

A traced run offers the requests of the mix's first ``trace_seconds``
alone: the profiler's collection at the close grows with the window,
and the per-layer metrics need a steady stretch, not the whole window.

Correctness: a sample drawn from the seed of the finished requests, the
longest among them, goes through the plain reference once (prompt and
served tokens), and the reading is the widest gap by which a served
token's logit lies below the reference's best at its position.
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np

from chipbench import harness, traffic_gen
from chipbench.drivers.serve_adapter import step_once, token_lists

WARM_RID = 10**9  # request ids of the warm-up, apart from the mix's
DRAIN_S = 60.0
SAMPLE = 4  # requests compared with the reference, the longest among them


def _capacity(t: dict) -> int:
    return max(t["prompt_lens"]) + t["max_new"][1]


def sample_requests(done: dict, reqs: list, seed: int) -> list:
    """The longest finished request and ``SAMPLE − 1`` others drawn from
    the seed."""
    fin = [r for r in reqs if len(done.get(r.rid, ())) == r.max_new]
    if not fin:
        return []
    longest = max(fin, key=lambda r: (r.prompt_len + r.max_new, -r.rid))
    rest = [r for r in fin if r.rid != longest.rid]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x5A]))
    pick = rng.choice(len(rest), size=min(SAMPLE - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_gaps(ctx, params, samples: list, served: dict, mode: str = "f32",
                   control: bool = False) -> float:
    """Widest gap, over the sampled requests' served positions, between
    the float32 reference's best logit and the logit of the served token
    (``control``: of the token that the ``mode`` reference ranks first)."""
    import jax
    import jax.numpy as jnp

    ref, t, m = ctx.reference(), ctx.traffic, ctx.model
    cap, count = _capacity(t), t["max_new"][1]
    seed32 = traffic_gen.seed32(ctx.seed)
    f = jax.jit(lambda p, x, s: ref.logits_at(p, x, s, count, m, "f32"))
    g = jax.jit(lambda p, x, s: ref.logits_at(p, x, s, count, m, mode))
    worst = 0.0
    for r in samples:
        toks = served[r.rid]
        prompt = traffic_gen.lm_tokens(seed32, r.rid, 1, r.prompt_len, m["vocab_size"])
        seq = np.zeros(cap, np.int32)
        seq[:r.prompt_len] = prompt[0, :r.prompt_len]
        seq[r.prompt_len:r.prompt_len + len(toks) - 1] = toks[:-1]
        start = jnp.int32(r.prompt_len - 1)
        want = np.asarray(f(params, jnp.asarray(seq), start))[:len(toks)]
        if control:
            toks = np.argmax(np.asarray(g(params, jnp.asarray(seq), start))[:len(toks)], -1)
        rows = np.arange(len(toks))
        worst = max(worst, float(np.max(want.max(-1) - want[rows, np.asarray(toks)])))
    return worst


def build(ctx):
    """Set-up: the benchmark's weights and the engine on them, with every
    program of the mix warmed: each prompt bucket, and every slot filled
    once."""
    import jax
    from repro.serve import ServeConfig, ServeEngine
    from repro.serve.trace import Request as EngineRequest

    t, m = ctx.traffic, ctx.model
    cfg = harness.model_config(m)
    params = ctx.reference().init_params(traffic_gen.jax_key(ctx.seed), m)
    harness.check_layout(params, cfg)
    engine = ServeEngine(cfg, params, ServeConfig(
        slots=t["slots"], scheduler=t["scheduler"], capacity=_capacity(t),
        seed=traffic_gen.seed32(ctx.seed), prefill_chunk=t["prefill_chunk"],
        prefill_batch=t["prefill_batch"]), [])
    lens = sorted(set(t["prompt_lens"]))
    warm = lens + [lens[0]] * max(t["slots"] - len(lens), 0)
    for i, n in enumerate(warm):
        engine.submit(EngineRequest(rid=WARM_RID + i, arrival=0.0, prompt_len=n,
                                    max_new=2, slo=math.inf))
    engine.run_until(math.inf)
    jax.block_until_ready(engine.pool.caches)
    return params, engine


def window(ctx, engine, reqs, trace: bool = False, seconds: float | None = None) -> dict:
    """Offer ``reqs`` open loop on the wall clock for ``seconds`` (default
    ``ctx.seconds``), then drain; token stamps and queue lengths, in
    seconds from the start."""
    import jax
    from repro.serve.trace import Request as EngineRequest

    seconds = ctx.seconds if seconds is None else seconds
    stamps = {r.rid: [] for r in reqs}
    late, queue = [], []
    with harness.traced_window(trace) as tw:
        t0 = time.perf_counter()
        i = 0
        while True:
            now = time.perf_counter() - t0
            while i < len(reqs) and reqs[i].arrival <= now:
                r = reqs[i]
                engine.submit(EngineRequest(rid=r.rid, arrival=r.arrival,
                                            prompt_len=r.prompt_len, max_new=r.max_new,
                                            slo=math.inf))
                late.append(now - r.arrival)
                i += 1
            if engine.has_work:
                queue.append((now, engine.n_queued))
                with jax.profiler.TraceAnnotation("bench.engine_step"):
                    step_once(engine)
                stamp = time.perf_counter() - t0
                with jax.profiler.TraceAnnotation("bench.stamp"):
                    for rid, toks in token_lists(engine).items():
                        if rid in stamps:
                            stamps[rid].extend([stamp] * (len(toks) - len(stamps[rid])))
            elif i < len(reqs):
                time.sleep(max(reqs[i].arrival - (time.perf_counter() - t0), 0.0))
            else:
                break
            if now > seconds + DRAIN_S:
                break
    served = {rid: list(v) for rid, v in token_lists(engine).items() if rid in stamps}
    return {"t0": t0, "stamps": stamps, "late": late, "queue": queue, "served": served,
            "trace_dir": tw["dir"], "end": time.perf_counter() - t0}


def summarize(ctx, reqs, w, seconds: float | None = None) -> dict:
    """End-to-end metrics of one window of ``seconds`` (default
    ``ctx.seconds``; ``setup_s`` aside)."""
    seconds = ctx.seconds if seconds is None else seconds
    inf = math.inf
    stamps = w["stamps"]
    ttft = [stamps[r.rid][0] - r.arrival if stamps[r.rid] else inf for r in reqs]
    gaps = [b - a for r in reqs for a, b in zip(stamps[r.rid], stamps[r.rid][1:])]
    failed = sum(len(stamps[r.rid]) < r.max_new for r in reqs)
    gaps += [inf] * failed  # an unfinished request's missing tokens rank last
    in_window = sum(x <= seconds for s in stamps.values() for x in s)
    return {
        "failed": failed,
        "serve_ttft_p95_ms": traffic_gen.window_quantile(ttft, 0.95) * 1e3,
        "serve_itl_p95_ms": traffic_gen.window_quantile(gaps, 0.95) * 1e3,
        "serve_tokens_per_s": in_window / seconds,
        "late_p95_ms": traffic_gen.window_quantile(w["late"], 0.95) * 1e3,
        "drain_s": w["end"] - seconds,
    }


def run(ctx, keep: bool = False) -> dict:
    """One run of the cell; ``keep`` also returns the weights, the sample
    and the served tokens, for the control."""
    import jax

    params, engine = build(ctx)
    reqs = traffic_gen.requests(ctx.traffic, ctx.seed, ctx.seconds)
    seconds = ctx.seconds
    if ctx.trace:
        seconds = min(seconds, ctx.traffic["trace_seconds"])
        reqs = [r for r in reqs if r.arrival < seconds]
    setup_s = time.perf_counter() - ctx.t_start
    w = window(ctx, engine, reqs, ctx.trace, seconds)
    mem = harness.memory_peak(jax.devices()[:1])
    del engine
    gc.collect()

    s = summarize(ctx, reqs, w, seconds)
    served = w["served"]
    samples = sample_requests(served, reqs, ctx.seed)
    t_ref = time.perf_counter()
    readings = {"token_gap": reference_gaps(ctx, params, samples, served) if samples else None}
    out = {
        "attempted": len(reqs), "failed": s["failed"],
        "metrics": {
            "serve_ttft_p95_ms": (s["serve_ttft_p95_ms"], "ms"),
            "serve_tokens_per_s": (s["serve_tokens_per_s"], "tokens/s"),
            "setup_s": (setup_s, "s"),
        },
        "readings": readings, "memory_peak_bytes": mem, "trace_dir": w["trace_dir"],
        "window_s": seconds,
        "notes": {"itl_p95_ms": s["serve_itl_p95_ms"], "late_p95_ms": s["late_p95_ms"],
                  "drain_s": s["drain_s"],
                  "reference_s": time.perf_counter() - t_ref,
                  "sampled": [r.rid for r in samples],
                  "served_compared": sum(len(served[r.rid]) for r in samples)},
        "facts": {"slots": ctx.traffic["slots"], "model": ctx.model, "requests": len(reqs)},
    }
    if keep:
        out["kept"] = {"params": params, "samples": samples, "served": served}
    return out
