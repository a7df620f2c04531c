"""The general traffic generator: deterministic per seed, the same work
for every seed, and the stated length distributions."""
import numpy as np
import pytest

from bench import traffic
from bench.tests import tiny

BIG_SEED = 2 ** 31 + 12345


@pytest.mark.parametrize("mix", [tiny.OPEN, tiny.CLOSED, traffic.load_mix("decode4k"),
                                 traffic.load_mix("decode16k")])
def test_deterministic_and_same_work_per_seed(mix):
    a = traffic.generate(mix, BIG_SEED, 512)
    b = traffic.generate(mix, BIG_SEED, 512)
    c = traffic.generate(mix, 7, 512)
    assert [r.prompt.tolist() for r in a] == [r.prompt.tolist() for r in b]
    assert [(r.due, r.max_new_tokens) for r in a] == [(r.due, r.max_new_tokens) for r in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    block = mix["block"]
    for i in range(0, len(a) - block + 1, block):
        blk = lambda rs: sorted((len(r.prompt), r.max_new_tokens) for r in rs[i:i + block])
        assert sorted(len(r.prompt) for r in a[i:i + block]) == \
            sorted(len(r.prompt) for r in c[i:i + block])
        assert sorted(r.max_new_tokens for r in a[i:i + block]) == \
            sorted(r.max_new_tokens for r in c[i:i + block])
        del blk
    # the arrival schedule ends at the same time for every seed
    assert a[block - 1].due == pytest.approx(c[block - 1].due)


def test_open_loop_lengths_and_rate():
    mix = dict(tiny.OPEN, requests=4096, block=256)
    reqs = traffic.generate(mix, 3, 512)
    plen = np.array([len(r.prompt) for r in reqs])
    olen = np.array([r.max_new_tokens for r in reqs])
    assert set(plen) <= {64, 128, 256}
    assert np.median(plen) == 128                   # lognormal median 90, rounded up
    assert 2 <= olen.min() and olen.max() <= 12
    assert np.median(olen) == pytest.approx(6, abs=1)
    assert traffic.prompt_buckets(mix) == [64, 128, 256]
    gaps = np.diff([0.0] + [r.due for r in reqs])
    assert np.mean(gaps) == pytest.approx(1 / mix["rate_per_s"], rel=0.05)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 512 for r in reqs)


def test_decode4k_sessions():
    mix = traffic.load_mix("decode4k")
    reqs = traffic.generate(mix, 5, 151936)
    first = reqs[:mix["slots"]]
    assert sorted(len(r.prompt) for r in first) == [2049] * 4 + [4097] * 4
    assert {r.max_new_tokens for r in reqs} == {1024}
    assert {r.due for r in reqs} == {0.0}
    assert 4097 - 1 + 1024 + 64 == mix["max_context"]


def test_decode16k_sessions():
    mix = traffic.load_mix("decode16k")
    reqs = traffic.generate(mix, 5, 32768)
    first = reqs[:mix["slots"]]
    assert sorted(len(r.prompt) for r in first) == [12289] * 4 + [16385] * 4
    assert {r.max_new_tokens for r in reqs} == {2048}
    assert {r.due for r in reqs} == {0.0}
    # prefill + budget + one step's headroom fit the context bound exactly
    assert 16385 - 1 + 2048 + 64 == mix["max_context"]


def test_seed_words_cover_large_and_negative_seeds():
    assert traffic._seed_words(2 ** 40 + 3) == [0, 3, 256]
    assert traffic._seed_words(-1) == [1, 1]
