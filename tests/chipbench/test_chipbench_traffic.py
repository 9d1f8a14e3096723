"""The traffic generator: a pure function of the seed and the mix."""

import collections
import json

import numpy as np

from chipbench import traffic_gen
from chipbench.harness import HERE

MIX = json.loads((HERE / "traffic" / "chat-poisson.json").read_text())
BIG = 2**40 + 12345


def test_same_seed_same_requests():
    assert traffic_gen.requests(MIX, BIG, 20.0) == traffic_gen.requests(MIX, BIG, 20.0)


def test_every_seed_offers_the_same_work_in_another_order():
    a = traffic_gen.requests(MIX, BIG, 20.0)
    b = traffic_gen.requests(MIX, BIG + 2**33, 20.0)
    assert len(a) == len(b)
    for key in ("prompt_len", "max_new", "arrival"):
        assert [getattr(r, key) for r in a] != [getattr(r, key) for r in b]
    for key in ("prompt_len", "max_new"):
        assert sorted(getattr(r, key) for r in a) == sorted(getattr(r, key) for r in b)


def test_arrivals_are_poisson():
    """Gaps as variable as exponential ones (coefficient of variation
    about 1), and counts per second as variable as Poisson counts (their
    variance about their mean), not spread evenly over the window."""
    seconds = 1000.0
    rs = traffic_gen.requests(MIX, BIG, seconds)
    t = np.array([r.arrival for r in rs])
    gaps = np.diff(t)
    assert 0.9 < gaps.std() / gaps.mean() < 1.1
    counts = np.histogram(t, bins=int(seconds), range=(0.0, seconds))[0]
    assert 0.8 < counts.var() / counts.mean() < 1.2
    sizes = np.array([r.prompt_len for r in rs])
    # sizes are independent of the arrival order: no layout spreads them
    half = len(sizes) // 2
    assert abs(np.mean(sizes[:half]) - np.mean(sizes[half:])) < 0.1 * np.mean(sizes)


def test_requests_fill_the_window_at_the_rate():
    rs = traffic_gen.requests(MIX, 7, 20.0)
    assert len(rs) == round(MIX["rate"] * 20.0)
    assert all(0.0 <= r.arrival < 20.0 for r in rs)
    assert all(a.arrival <= b.arrival for a, b in zip(rs, rs[1:]))
    counts = collections.Counter(r.prompt_len for r in rs)
    w = dict(zip(MIX["prompt_lens"], MIX["prompt_weights"]))
    for n, c in counts.items():
        assert abs(c - len(rs) * w[n] / sum(w.values())) < 1
    lo, hi = MIX["max_new"]
    assert all(lo <= r.max_new <= hi for r in rs)


def test_prompts_match_the_engines_generator():
    from repro.data.synthetic import lm_tokens

    s = traffic_gen.seed32(BIG)
    assert np.array_equal(traffic_gen.lm_tokens(s, 17, 1, 300, 65536),
                          lm_tokens(s, 17, 1, 300, 65536))


def test_train_tokens_rows_all_differ():
    a = traffic_gen.train_tokens(BIG, 0, 4, 4, 256, 49155)
    b = traffic_gen.train_tokens(BIG, 1, 4, 4, 256, 49155)
    assert a.shape == (4, 4, 256) and a.dtype == np.int32
    rows = [tuple(x) for x in np.concatenate([a, b]).reshape(-1, 256)]
    assert len(set(rows)) == len(rows)
    assert np.array_equal(a, traffic_gen.train_tokens(BIG, 0, 4, 4, 256, 49155))


def test_seeds_keep_their_high_bits():
    assert traffic_gen.seed32(5) != traffic_gen.seed32(5 + 2**32)
    k1, k2 = traffic_gen.jax_key(5), traffic_gen.jax_key(5 + 2**32)
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))


def test_quantile_ranks_unanswered_last():
    assert traffic_gen.window_quantile([1.0, 2.0, 3.0, float("inf")], 0.95) == float("inf")
    assert traffic_gen.window_quantile(list(range(1, 101)), 0.95) == 95
