import numpy as np

from bench import spec
from bench.harness import LOAD_AFTER_S
from bench.traffic import Traffic

BIG_SEED = 2**31 + 12345


def _items(mix, seed, n=150):
    t = Traffic(mix, seed, 151936)
    return t, [t.item(k) for k in range(n)]


def _mix(name):
    return spec.load_json(spec.traffic_file(name))


def test_identical_per_seed():
    _, a = _items(_mix("chat"), BIG_SEED)
    _, b = _items(_mix("chat"), BIG_SEED)
    for x, y in zip(a, b):
        assert x.arrival_s == y.arrival_s and x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)


def test_every_seed_sends_the_same_schedule():
    _, a = _items(_mix("chat"), 1)
    _, b = _items(_mix("chat"), BIG_SEED)
    assert [(x.arrival_s, len(x.prompt), x.max_new) for x in a] == [
        (x.arrival_s, len(x.prompt), x.max_new) for x in b]
    assert all(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # A seed of more than 32 bits is not folded onto a small one.
    _, c = _items(_mix("chat"), BIG_SEED + 2**32)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(b, c))


def test_schedule_seed_draws_another_schedule():
    mix = _mix("chat")
    _, a = _items(mix, 1)
    _, b = _items(dict(mix, schedule_seed=mix["schedule_seed"] + 1), 1)
    assert [x.arrival_s for x in a] != [x.arrival_s for x in b]
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]


def test_arrivals_are_poisson():
    """Exponential gaps of mean 1 / rate: their coefficient of variation is
    near one, where an even spacing's would be near zero."""
    mix = _mix("chat")
    t = Traffic(mix, 1, 151936)
    gaps = np.diff(t.arrivals)
    assert t.arrivals[0] == 0.0 and (gaps > 0).all()
    assert abs(gaps.mean() * mix["rate_per_s"] - 1) < 0.2
    assert 0.75 < gaps.std() / gaps.mean() < 1.25
    # A rate scales the same draws.
    fast = Traffic(dict(mix, rate_per_s=2 * mix["rate_per_s"]), 1, 151936)
    np.testing.assert_allclose(fast.arrivals, t.arrivals / 2)


def test_lengths_follow_the_mix():
    for name in ("chat", "gen-batch"):
        mix = _mix(name)
        t = Traffic(mix, 1, 151936)
        for sizes, dist in ((t.prompt_sizes, mix["prompt_len"]),
                            (t.output_sizes, mix["output_len"])):
            assert sizes.min() >= dist["min"] and sizes.max() <= dist["max"]
            assert abs(np.median(sizes) / dist["median"] - 1) < 0.2
            assert len(set(sizes.tolist())) > len(sizes) // 3


def test_open_loop_schedule_outlasts_the_run():
    """The chat schedule holds arrivals past the window and the load kept
    on after it, at the benchmark's run length."""
    t = Traffic(_mix("chat"), 1, 151936)
    horizon = spec.benchmark()["run_seconds"] + LOAD_AFTER_S
    assert t.arrivals[-1] > horizon
    items = t.arriving(horizon)
    assert items and items[-1].arrival_s < horizon
    assert t.prompt_lengths(horizon) == sorted({len(x.prompt)
                                               for x in items})


def test_backlog_has_no_arrivals_and_cycles():
    mix = _mix("gen-batch")
    t = Traffic(mix, 5, 32000)
    assert not t.open_loop and t.item(3).arrival_s is None
    n = mix["requests"]
    assert len(t.item(n + 3).prompt) == len(t.item(3).prompt)
    assert t.prompt_lengths() == sorted(set(t.prompt_sizes.tolist()))
