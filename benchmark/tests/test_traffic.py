"""The generator's stratification: every seed offers the same multiset
of lengths and gaps, in another order."""

import numpy as np
import pytest

from benchmark.lib import harness, traffic

SEEDS = (3, 2 ** 31 + 11)


def _window(schedule):
    return [r for r in schedule if r.due_s >= 0]


def _cycle(t, schedule):
    """One complete stratified set of the schedule: the requests due in
    the window (or, where all are due at once, the last cycle)."""
    n = round(t["arrivals"]["rate_per_s"] * 45.0)
    if t["shared_prefix"]:
        n -= n % t["shared_prefix"]["asks"]
    return schedule[-n:]


@pytest.mark.parametrize("name", ["chat-steady", "docs-batch"])
def test_same_multiset_other_order(name):
    t = harness.traffic(name)
    a, b = (traffic.serve_schedule(t, 50257, s, 45.0) for s in SEEDS)
    ca, cb = _cycle(t, a), _cycle(t, b)
    own = lambda r: r.tokens.size - r.shared  # noqa: E731
    for pick in (own, lambda r: r.max_new, lambda r: r.shared):
        assert sorted(map(pick, ca)) == sorted(map(pick, cb))
    assert [own(r) for r in ca] != [own(r) for r in cb]
    assert [r.max_new for r in ca] != [r.max_new for r in cb]
    assert not np.array_equal(a[-1].tokens, b[-1].tokens)
    for r in a + b:
        assert r.tokens.size + r.max_new <= 1024
        assert r.tokens.min() >= 0 and r.tokens.max() < 50257


def test_chat_gaps_and_cycle():
    t = harness.traffic("chat-steady")
    a, b = (traffic.serve_schedule(t, 50257, s, 45.0) for s in SEEDS)
    n = round(t["arrivals"]["rate_per_s"] * 45.0)
    gaps = lambda s: np.diff([r.due_s for r in _window(s)] + [45.0])  # noqa: E731
    assert len(_window(a)) == n
    np.testing.assert_allclose(np.sort(gaps(a)), np.sort(gaps(b)), rtol=1e-9, atol=1e-9)
    assert gaps(a).sum() == pytest.approx(45.0)
    # the warm-up replays the end of the same cycle: same lengths, fresh ids
    warm = [r for r in a if r.due_s < 0]
    tail = _window(a)[-len(warm):]
    assert [r.max_new for r in warm] == [r.max_new for r in tail]
    assert [r.due_s + 45.0 for r in warm] == pytest.approx([r.due_s for r in tail])
    assert not np.array_equal(warm[0].tokens, tail[0].tokens)
    assert min(r.due_s for r in a) >= -t["arrivals"]["warmup_s"]
    # same seed, same schedule
    again = traffic.serve_schedule(t, 50257, SEEDS[0], 45.0)
    assert all(np.array_equal(x.tokens, y.tokens) and x.due_s == y.due_s
               for x, y in zip(a, again))


def test_docs_share_a_prefix_in_fours():
    t = harness.traffic("docs-batch")
    s = traffic.serve_schedule(t, 50257, 5, 45.0)
    asks = t["shared_prefix"]["asks"]
    assert len(s) % asks == 0
    for g in range(0, len(s), asks):
        group = s[g:g + asks]
        k = group[0].shared
        assert t["shared_prefix"]["min"] <= k <= t["shared_prefix"]["max"]
        assert all(r.shared == k for r in group)
        assert all(np.array_equal(r.tokens[:k], group[0].tokens[:k]) for r in group)
        assert len({r.tokens[k:].tobytes() for r in group}) == asks
    assert all(r.due_s == -t["arrivals"]["warmup_s"] for r in s)


def test_length_list_is_quantile_midpoints():
    spec = {"dist": "uniform", "min": 0, "max": 100}
    assert traffic.length_list(spec, 4).tolist() == [12, 38, 62, 88]
    ln = traffic.length_list({"dist": "lognormal", "median": 160, "sigma": 0.4,
                              "min": 96, "max": 320}, 25)
    assert ln[12] == 160 and ln.min() >= 96 and ln.max() <= 320
    assert (np.diff(ln) >= 0).all()


def test_train_rows_from_seed():
    t = harness.traffic("pretrain-1k")
    a = traffic.train_rows(t, 50257, 2 ** 31 + 5, 6)
    assert a.shape == (6, 1024) and a.dtype == np.int32
    assert np.array_equal(a, traffic.train_rows(t, 50257, 2 ** 31 + 5, 6))
    assert not np.array_equal(a, traffic.train_rows(t, 50257, 7, 6))
    assert len({r.tobytes() for r in a}) == 6
