import numpy as np
import pytest

from bench import spec
from bench import traffic as T


def _mix(name):
    return T.Traffic.from_dict(spec.load_json(
        spec.BENCH_DIR / "traffic" / f"{name}.json"))


@pytest.mark.parametrize("name", ["decode-heavy", "long-context"])
def test_draws_are_seeded_and_in_range(name):
    tr = _mix(name)
    seed = 2**33 + 5                      # wider than 32 signed bits
    for c in range(tr.clients):
        for k in range(4):
            a = T.draw(tr, seed, 50304, c, k)
            b = T.draw(tr, seed, 50304, c, k)
            np.testing.assert_array_equal(a.prompt, b.prompt)
            assert a.max_new_tokens == b.max_new_tokens
            assert a.prompt.dtype == np.int32
            assert 0 <= a.prompt.min() and a.prompt.max() < 50304
            total = len(a.prompt) + a.max_new_tokens
            assert tr.prompt.min + tr.output.min <= total <= tr.max_tokens
            if k:     # a fresh request draws both lengths in range
                assert tr.prompt.min <= len(a.prompt) <= tr.prompt.max
                assert tr.output.min <= a.max_new_tokens <= tr.output.max
            else:     # in flight: part of its output already done
                assert 1 <= a.max_new_tokens <= tr.output.max
    # another seed: other tokens, the same work
    for c in range(tr.clients):
        a, b = T.draw(tr, seed, 50304, c, 1), T.draw(tr, 7, 50304, c, 1)
        assert len(a.prompt) == len(b.prompt)
        assert a.max_new_tokens == b.max_new_tokens
        assert not np.array_equal(a.prompt, b.prompt)


def test_in_flight_phases_are_spread_over_the_clients():
    tr = _mix("decode-heavy")
    n = tr.clients
    for c in range(n):
        p, left = T.lengths(tr, c, 0)
        # client c has between c/n and (c+1)/n of its drawn output left
        assert tr.output.min * c / n <= left
        assert left <= -(-tr.output.max * (c + 1) // n)
        assert tr.prompt.min <= p <= tr.max_tokens - left


def test_lognormal_lengths_follow_the_file():
    tr = _mix("decode-heavy")
    rng = np.random.default_rng(0)
    xs = np.array([tr.prompt.draw(rng) for _ in range(4000)])
    assert xs.min() >= 64 and xs.max() <= 512
    assert abs(np.median(xs) - 192) < 12


def test_pool_holds_every_client_at_its_longest():
    for name in ("decode-heavy", "long-context"):
        tr = _mix(name)
        per = -(-tr.max_tokens // 16)
        assert tr.pool_blocks >= tr.clients * per + 1


@pytest.mark.parametrize("bad", [
    {"loop": "open"}, {"clients": 0},
    {"prompt_tokens": {"dist": "zipf", "min": 1, "max": 2}}])
def test_bad_traffic_is_refused(bad):
    d = spec.load_json(spec.BENCH_DIR / "traffic" / "decode-heavy.json")
    d.update(bad)
    with pytest.raises((ValueError, TypeError)):
        T.Traffic.from_dict(d)
