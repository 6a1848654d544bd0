"""The traffic generator: the seed orders the work, never changes it."""
import collections
import math

import numpy as np
import pytest

from bench import spec
from bench.traffic import Traffic, lengths

CHAT = spec.traffic("chat")
BIG_SEED = 2**33 + 12345


def _streams(spec_, seed, per_client):
    """Each client's requests, in the order it sends them."""
    t = Traffic(spec_, seed, vocab=1000)
    return [[t.next(k) for _ in range(per_client)]
            for k in range(t.clients)]


def _sizes(streams):
    return [[(len(d.prompt), d.max_new) for d in s] for s in streams]


def test_same_seed_same_requests():
    a, b = _streams(CHAT, BIG_SEED, 7), _streams(CHAT, BIG_SEED, 7)
    for x, y in zip(sum(a, []), sum(b, [])):
        assert x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)


def test_every_seed_serves_the_same_streams_to_other_clients():
    a, b = _sizes(_streams(CHAT, 1, 10)), _sizes(_streams(CHAT, BIG_SEED, 10))
    assert sorted(a) == sorted(b)          # the same work ...
    assert a != b                          # ... dealt to other clients
    # 8 clients x 5 requests cover the pool of 40 once, the first
    # request of each stream cut short
    t = Traffic(CHAT, 1, vocab=10)
    firsts = collections.Counter(x for s in a for x in s[1:5])
    firsts.update((t.pool[s][0], t.pool[s][1]) for s in range(t.clients))
    assert firsts == collections.Counter(t.pool)
    ta, tb = _streams(CHAT, 1, 1), _streams(CHAT, BIG_SEED, 1)
    assert not np.array_equal(ta[0][0].prompt[:8], tb[0][0].prompt[:8])


def test_the_loop_starts_staggered():
    """Stream s's first answer keeps ceil(n (s + 1/2) / C) of its n
    tokens, so the clients' first requests end at spread-out steps."""
    t = Traffic(CHAT, BIG_SEED, vocab=10)
    c = t.clients
    firsts = {t._stream[k]: t.next(k) for k in range(c)}
    for s, d in firsts.items():
        p, out = t.pool[s]
        assert len(d.prompt) == p
        assert d.max_new == math.ceil(out * (s + 0.5) / c)
        assert 1 <= d.max_new <= out
    assert len({d.max_new for d in firsts.values()}) == c
    # the next request of every client is whole
    for k in range(c):
        s = t._stream[k]
        assert t.next(k).max_new == t.pool[s + c][1]


def test_chat_lengths_follow_the_file():
    t = Traffic(CHAT, 0, vocab=10)
    counts = collections.Counter(p for p, _ in t.pool)
    assert counts == {8: 10, 13: 10, 20: 10, 32: 10}
    outs = sorted(o for _, o in t.pool)
    assert outs[0] >= 1 and outs[-1] <= 224
    assert np.median(outs) == pytest.approx(49, abs=2)
    # the published means (Alpaca in the vLLM evaluation: 19.31, 58.45)
    assert np.mean(outs) == pytest.approx(58.45, rel=0.02)
    assert np.mean([p for p, _ in t.pool]) == pytest.approx(19.31, rel=0.06)
    assert t.prompt_lengths() == [8, 13, 20, 32]
    assert t.max_len() == 32 + 224


def test_lognormal_quantiles_clip():
    spec_ = {"lognormal": {"median": 48, "sigma": 2.0}, "min": 16,
             "max": 128}
    out = lengths(spec_, 100)
    assert out == sorted(out) and min(out) == 16 and max(out) == 128


def test_tokens_stay_in_vocab():
    for d in sum(_streams(CHAT, 7, 8), []):
        assert d.prompt.dtype == np.int32
        assert 0 <= d.prompt.min() and d.prompt.max() < 1000
