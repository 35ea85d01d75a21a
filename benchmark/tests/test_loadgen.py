"""The traffic generator: same seed, same traffic; every block of
requests, the same set of lengths in another order. Run by hand:
`python -m pytest benchmark/tests -q -p no:cacheprovider`."""
import json
import os

import numpy as np

from benchmark.harness.loadgen import (ServeTraffic, TokenStream, gap_set,
                                       length_set)

HERE = os.path.dirname(os.path.abspath(__file__))


def mix(name):
    with open(os.path.join(HERE, "..", "traffic", name + ".json")) as f:
        return json.load(f)


def test_same_seed_same_traffic():
    m = mix("serve.closed64")
    a, b = (ServeTraffic(m, 50304, 3_000_000_123) for _ in range(2))
    assert np.array_equal(a.prompt_len, b.prompt_len)
    assert np.array_equal(a.max_new, b.max_new)
    assert all(np.array_equal(a.prompt(k), b.prompt(k)) for k in (0, 7, 500))


def test_the_seed_draws_the_tokens_and_not_the_schedule():
    m = mix("serve.closed64")
    a, b = ServeTraffic(m, 50304, 1), ServeTraffic(m, 50304, 2**31 + 5)
    assert np.array_equal(a.prompt_len, b.prompt_len)
    assert np.array_equal(a.max_new, b.max_new)
    assert not np.array_equal(a.prompt(0), b.prompt(0))


def test_every_block_offers_the_same_set_of_lengths():
    m = mix("serve.closed64")
    t = ServeTraffic(dict(m, arrival={"mode": "poisson", "rate_rps": 2.0}), 50304, 1)
    blk = m["block"]
    first = sorted(zip(t.prompt_len[:blk], t.max_new[:blk]))
    for lo in (blk, 5 * blk):
        assert sorted(zip(t.prompt_len[lo:lo + blk], t.max_new[lo:lo + blk])) == first
    assert not np.array_equal(t.prompt_len[:blk], t.prompt_len[blk:2 * blk])


def test_first_replies_of_a_closed_loop_are_staggered():
    m = mix("serve.closed64")
    c = m["arrival"]["clients"]
    t = ServeTraffic(m, 50304, 1)
    whole = ServeTraffic(dict(m, arrival={"mode": "poisson", "rate_rps": 2.0}),
                         50304, 1).max_new
    assert np.all(t.max_new[:c] <= whole[:c]) and t.max_new[c - 1] == whole[c - 1]
    assert np.array_equal(t.max_new[c:], whole[c:])
    assert t.max_new[0] == max(1, -(-whole[0] // c))


def test_length_distributions():
    m = mix("serve.closed64")
    p = length_set(m["prompt_len"], m["block"])
    o = length_set(m["output_len"], m["block"])
    assert p.min() >= 32 and p.max() <= 768 and o.min() >= 16 and o.max() <= 256
    assert abs(np.median(p) - 192) <= 8 and abs(np.median(o) - 96) <= 4
    # a heavy tail: the mean sits well above the median
    assert p.mean() > 1.15 * np.median(p)
    # prompt + reply always fits the engine's context
    assert (p.max() + o.max()) <= 1024
    t = ServeTraffic(m, 50304, 0)
    assert len(t.prompt(3)) == t.prompt_len[3]
    assert t.prompt(3).dtype == np.int32 and t.prompt(3).max() < 50304


def test_open_loop_gaps_keep_the_rate_and_the_burstiness():
    arr = {"mode": "gamma", "rate_rps": 8.0, "burstiness": 2.0}
    g = gap_set(arr, 4096)
    assert abs(g.mean() - 1 / 8.0) < 1e-12
    cv2 = g.var() / g.mean() ** 2
    assert 1.6 < cv2 < 2.4
    m = dict(mix("serve.closed64"), arrival=arr)
    a, b = ServeTraffic(m, 50304, 1), ServeTraffic(m, 50304, 2)
    assert not a.closed and a.dues[0] == 0.0 and np.all(np.diff(a.dues) >= 0)
    blk = m["block"]
    # the same gaps in another order
    assert np.allclose(np.sort(np.diff(a.dues[:blk + 1])),
                       np.sort(np.diff(b.dues[:blk + 1])))
    assert not np.allclose(np.diff(a.dues[:blk + 1]), np.diff(b.dues[:blk + 1]))


def test_shared_prefix_pool():
    m = dict(mix("serve.closed64"),
             shared_prefix={"len": 64, "pool": 4, "zipf": 1.1})
    t = ServeTraffic(m, 50304, 9)
    heads = {tuple(t.prompt(k)[:64]) for k in range(200)}
    assert 1 < len(heads) <= 4
    assert len(t.prompt(0)) == 64 + t.prompt_len[0]


def test_token_stream():
    m = mix("train.b8s1024")
    a, b = TokenStream(m, 50304, 1024, 77), TokenStream(m, 50304, 1024, 77)
    ids, labels = a[5]
    assert ids.shape == labels.shape == (1024,) and ids.dtype == np.int32
    assert np.array_equal(ids[1:], labels[:-1])
    assert np.array_equal(ids, b[5][0]) and not np.array_equal(ids, a[6][0])
    assert not np.array_equal(ids, TokenStream(m, 50304, 1024, 78)[5][0])
    # zipf: a few tokens carry most of the mass, so there is something to learn
    _, counts = np.unique(np.concatenate([a[i][0] for i in range(8)]),
                          return_counts=True)
    assert np.sort(counts)[-100:].sum() > 0.4 * counts.sum()
