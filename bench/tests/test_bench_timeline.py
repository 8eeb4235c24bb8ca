"""A stall injected into a synthetic timeline moves every end-to-end
metric the way a user would feel it."""
from bench import timeline
from bench.timeline import ReqRec

SECONDS = 10.0


def _timeline(stall_every=None, stall=0.3):
    """One request a second, 20 tokens each 50 ms apart after a 200 ms
    prefill.  A stall of ``stall`` seconds every ``stall_every`` seconds
    delays everything the engine emits after it; arrivals keep their
    schedule."""
    def warp(t):
        return t if stall_every is None else t + stall * (t // stall_every)

    reqs = []
    for i in range(10):
        arrival = float(i)
        times = [warp(arrival + 0.2 + 0.05 * j) for j in range(20)]
        reqs.append(ReqRec(rid=i, arrival=arrival, prompt_len=100,
                           max_new=20, submitted=arrival, token_times=times,
                           done=True))
    return reqs


def test_stall_moves_every_metric():
    base, stalled = _timeline(), _timeline(stall_every=0.45)
    end = 12.0
    assert timeline.ttft_p90_ms(stalled, SECONDS, end) > timeline.ttft_p90_ms(
        base, SECONDS, end)
    assert timeline.itl_p95_ms(stalled, SECONDS) > timeline.itl_p95_ms(
        base, SECONDS)
    assert timeline.tokens_per_s(stalled, SECONDS) < timeline.tokens_per_s(
        base, SECONDS)


def test_clock_starts_at_the_scheduled_arrival():
    late = _timeline()
    for r in late:
        r.submitted = r.arrival + 0.5       # the generator ran late
    assert timeline.ttft_p90_ms(late, SECONDS, 12.0) == timeline.ttft_p90_ms(
        _timeline(), SECONDS, 12.0)
    assert timeline.lateness_ms(late)[0] == 500.0


def test_a_request_without_a_first_token_counts_until_the_end():
    reqs = _timeline()
    for r in reqs[:2]:
        r.token_times = []
    assert timeline.ttft_p90_ms(reqs, SECONDS, 30.0) >= 28_000


def test_rates_count_prompt_once_and_only_inside_the_window():
    reqs = _timeline()
    n_in = sum(1 for r in reqs for t in r.token_times if t < SECONDS)
    prompts = sum(r.prompt_len for r in reqs if r.token_times[0] < SECONDS)
    assert timeline.tokens_per_s(reqs, SECONDS) == (n_in + prompts) / SECONDS
