"""Serving launcher (DESIGN.md §14).

Two modes:

  * **engine** (``--trace poisson|bursty``): drive the continuous-
    batching ``repro.serve`` engine from an open-loop arrival trace —
    bounded slot pool, per-step eviction + backfill, ``fcfs`` or
    ``deadline`` admission, per-request SLO accounting. With
    ``--track-training`` a co-running sharded trainer commits to a live
    PS and the replica pulls version-stale shards between decode steps.
    ``--prefill-chunk C`` turns on chunked prefill (C tokens per
    dispatch, interleaved with decode; ``--prefill-batch`` lanes share
    each dispatch) and ``--replicas N`` puts N engines behind a
    ``--router`` policy (§17).

        PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b \
            --smoke --trace poisson --requests 32 --rate 20 --slots 4 \
            --scheduler deadline --slo-ms 800 --metrics run.jsonl

        PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b \
            --smoke --trace bursty --requests 64 --rate 40 \
            --prefill-chunk 16 --prefill-batch 2 \
            --replicas 2 --router deadline_slack

  * **one-shot** (no ``--trace``): the original fixed-batch demo —
    prefill a batch of prompts, greedy-decode ``--new-tokens``.

        PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b \
            --smoke --batch 4 --prompt-len 32 --new-tokens 16

Both print wall timings; the engine also reports the virtual-clock
latency distribution (deterministic across hosts).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke
from repro.data.synthetic import lm_tokens
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--seed", type=int, default=0)
    # one-shot mode
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--new-tokens", type=int, default=16)
    # engine mode
    p.add_argument("--trace", default="", help="poisson|bursty — enables the "
                   "continuous-batching engine (default: one-shot demo)")
    p.add_argument("--requests", type=int, default=32)
    p.add_argument("--rate", type=float, default=16.0, help="mean arrivals/s")
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--scheduler", default="fcfs", help="fcfs|deadline")
    p.add_argument("--mode", default="continuous", help="continuous|static")
    p.add_argument("--slo-ms", type=float, default=1000.0)
    p.add_argument("--prefill-chunk", type=int, default=0,
                   help="tokens per chunked-prefill dispatch (0 = monolithic)")
    p.add_argument("--prefill-batch", type=int, default=1,
                   help="prefill lanes sharing each chunk dispatch")
    p.add_argument("--replicas", type=int, default=1,
                   help="engine replicas behind the load balancer")
    p.add_argument("--router", default="least_queue",
                   help="round_robin|least_queue|deadline_slack")
    p.add_argument("--metrics", default="", help="stream JSONL records here")
    p.add_argument("--track-training", action="store_true",
                   help="co-run a sharded trainer; pull stale shards live")
    p.add_argument("--sync-every", type=int, default=4,
                   help="decode steps between PS polls (with --track-training)")
    p.add_argument("--shards", type=int, default=4,
                   help="PS shard count (with --track-training)")
    return p


# ---------------------------------------------------------------------------
# one-shot mode (fixed-batch prefill + decode demo)
# ---------------------------------------------------------------------------


def run_oneshot(args) -> dict:
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    rng = np.random.default_rng(args.seed)
    prompts = lm_tokens(args.seed, 0, args.batch, args.prompt_len, cfg.vocab_size)[:, :-1]
    batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
    if cfg.frontend == "vision":
        batch["patches"] = jnp.asarray(
            rng.normal(size=(args.batch, cfg.num_prefix_embeddings, cfg.d_model)) * 0.02,
            jnp.float32)
    if cfg.encoder is not None:
        batch["frames"] = jnp.asarray(
            rng.normal(size=(args.batch, cfg.encoder.num_frames, cfg.encoder.d_model)) * 0.02,
            jnp.float32)

    params = lm.lm_init_cast(jax.random.PRNGKey(args.seed), cfg)
    prefill = jax.jit(lambda p_, b: lm.lm_prefill(
        cfg, p_, b, reserve=args.new_tokens + 1))
    decode = jax.jit(lambda p_, t, c: lm.lm_decode_step(cfg, p_, t, c))

    t0 = time.time()
    last_logits, caches = prefill(params, batch)
    jax.block_until_ready(last_logits)
    t_prefill = time.time() - t0
    next_tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)[:, None]

    # first generated token is the prefill argmax; the decode loop
    # produces the remaining new_tokens - 1 (zero when --new-tokens 1)
    n_decoded = max(args.new_tokens - 1, 0)
    out_tokens = [next_tok]
    t0 = time.time()
    for _ in range(n_decoded):
        logits, caches = decode(params, {"tokens": next_tok}, caches)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        out_tokens.append(next_tok)
    jax.block_until_ready(next_tok)
    t_decode = time.time() - t0

    generated = np.concatenate([np.asarray(t) for t in out_tokens], axis=1)
    stats = {
        "arch": cfg.name, "batch": args.batch, "prompt_len": args.prompt_len,
        "n_decoded": n_decoded, "t_prefill": t_prefill, "t_decode": t_decode,
        "prefill_tok_s": args.batch * args.prompt_len / max(t_prefill, 1e-9),
        # decode throughput counts decode-loop tokens only — the first
        # generated token came out of prefill and is already paid there
        "decode_ms_per_token": (t_decode * 1e3 / n_decoded) if n_decoded else None,
        "decode_tok_s": (args.batch * n_decoded / max(t_decode, 1e-9)
                         if n_decoded else None),
        "generated": generated,
    }
    print(f"# arch={cfg.name} batch={args.batch} prompt={args.prompt_len}")
    print(f"# prefill: {t_prefill*1e3:.1f} ms "
          f"({stats['prefill_tok_s']:.0f} tok/s)")
    if n_decoded:
        print(f"# decode:  {stats['decode_ms_per_token']:.1f} ms/token "
              f"({stats['decode_tok_s']:.0f} tok/s, {n_decoded} steps)")
    else:
        print("# decode:  skipped (--new-tokens 1: the only generated token "
              "is the prefill argmax)")
    for i in range(min(args.batch, 2)):
        print(f"seq{i}: {generated[i].tolist()}")
    return stats


# ---------------------------------------------------------------------------
# engine mode (continuous batching over an open-loop trace)
# ---------------------------------------------------------------------------


def run_engine(args) -> dict:
    from repro.fleet import JsonlSink, MetricsLog
    from repro.fleet.metrics import span_stream
    from repro.serve import (LoadBalancer, ReplicaSync, ServeConfig,
                             ServeEngine, ShardedTrainer, TraceConfig,
                             make_trace)

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    params = lm.lm_init_cast(jax.random.PRNGKey(args.seed), cfg)
    tc = TraceConfig(n_requests=args.requests, rate=args.rate,
                     slo_ms=args.slo_ms, seed=args.seed)
    trace = make_trace(args.trace, tc)
    serve_cfg = ServeConfig(
        slots=args.slots, scheduler=args.scheduler, mode=args.mode,
        sync_every=args.sync_every if args.track_training else 0,
        seed=args.seed, prefill_chunk=args.prefill_chunk,
        prefill_batch=args.prefill_batch)

    trainer = tick = None
    make_sync = None
    loss_first = loss_last = None
    if args.track_training:
        trainer = ShardedTrainer(cfg, params, n_shards=args.shards)
        make_sync = lambda i: ReplicaSync(  # noqa: E731
            params, lambda: trainer.state, n_shards=args.shards)
        tick = lambda eng, t: trainer.advance(t)  # noqa: E731
        loss_first = trainer.eval_loss(params)

    sink = JsonlSink(args.metrics) if args.metrics else MetricsLog()
    t0 = time.time()
    balance = None
    # the engine's spans join the --metrics stream as the run ends
    with span_stream(sink if args.metrics else None):
        if args.replicas > 1:
            balancer = LoadBalancer(cfg, params, serve_cfg, trace,
                                    n_replicas=args.replicas, router=args.router,
                                    metrics=sink, make_sync=make_sync, tick=tick)
            balance = balancer.run()
            report = balance.merged
            engines = balancer.engines
        else:
            engine = ServeEngine(cfg, params, serve_cfg, trace, metrics=sink,
                                 sync=make_sync(0) if make_sync else None,
                                 tick=tick)
            report = engine.run()
            engines = [engine]
    synced_params = engines[0].params
    wall = time.time() - t0
    if args.track_training:
        loss_last = trainer.eval_loss(synced_params)
    if isinstance(sink, JsonlSink):
        sink.close()

    print(f"# arch={cfg.name} trace={args.trace} requests={args.requests} "
          f"rate={args.rate}/s slots={args.slots} scheduler={args.scheduler} "
          f"mode={args.mode} chunk={args.prefill_chunk} "
          f"replicas={args.replicas}")
    print(f"# served {len(report.records)} requests, "
          f"{report.total_tokens} tokens in {report.t_end:.2f} virtual s "
          f"({wall:.1f} s wall)")
    print(f"# latency total p50 {report.percentile('total', 0.5)*1e3:.1f} ms "
          f"p99 {report.percentile('total', 0.99)*1e3:.1f} ms | "
          f"queue p99 {report.percentile('queue', 0.99)*1e3:.1f} ms")
    print(f"# SLO attainment {100*report.slo_attainment:.1f}% | "
          f"goodput {report.goodput:.2f} req/s | "
          f"{report.tokens_per_s:.1f} tok/s")
    if args.prefill_chunk:
        print(f"# chunked prefill: {report.chunk_dispatches} dispatches "
              f"(chunk {args.prefill_chunk}, {args.prefill_batch} lanes)")
    if balance is not None:
        print(f"# router={balance.router} per-replica requests "
              f"{balance.per_replica_requests}")
    if args.track_training:
        print(f"# training: loss {loss_first:.4f} -> {loss_last:.4f} over "
              f"{trainer.commits} commits | pulls {report.sync_pulls}/"
              f"{report.sync_polls} polls, {report.pull_bytes/1e6:.2f} MB "
              f"(dense re-pull would be {report.full_pull_bytes/1e6:.2f} MB)")
    if args.metrics:
        print(f"# metrics -> {args.metrics}")
    return {"report": report, "loss_first": loss_first, "loss_last": loss_last,
            "trainer": trainer, "balance": balance, "engines": engines,
            "trace": trace}


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    if args.trace:
        return run_engine(args)
    return run_oneshot(args)


if __name__ == "__main__":
    main()
