"""End-to-end arithmetic on fake timestamps."""
import numpy as np

from bench import e2e

W = (10.0, 20.0)


def log(times, due=None, i=0):
    return e2e.ClientLog(i, due=due, token_times=list(times))


def test_rate_is_over_the_whole_window():
    logs = [log([10.5, 11.0, 12.0]), log([9.0, 19.9, 20.0, 25.0])]
    # tokens at 9.0, 20.0 and 25.0 fall outside [10, 20)
    assert e2e.output_tokens_per_s(logs, W) == 4 / 10.0


def test_itl_tail_covers_all_gaps_inside_the_window():
    steady = [log(np.arange(10.0, 12.0, 0.1) + 0.01 * k, i=k)
              for k in range(4)]
    base = e2e.p95(e2e.itl_samples(steady, W))
    assert abs(base - 0.1) < 1e-9
    # one 3 s stall is one gap among 77: below the 95th percentile
    one = [log([12.0, 15.0], i=9)] + steady
    gaps = e2e.itl_samples(one, W)
    assert len(gaps) == 4 * 19 + 1 and gaps.max() == 3.0
    assert abs(e2e.p95(gaps) - 0.1) < 1e-9
    # a stall every request sees (a long prefill in one step) moves it
    stalled = [log(list(np.arange(10.0, 12.0, 0.1) + 0.01 * k)
                   + [15.0 + 0.01 * k, 18.0 + 0.01 * k], i=k)
               for k in range(4)]
    assert e2e.p95(e2e.itl_samples(stalled, W)) > 1.0


def test_itl_needs_both_tokens_inside():
    g = log([9.9, 10.2, 19.95, 20.1])
    assert np.allclose(e2e.itl_samples([g], W), [19.95 - 10.2])


def test_unanswered_request_counts_in_ttft():
    answered = [log([10.0 + k + 0.2], due=10.0 + k, i=k) for k in range(9)]
    waiting = log([], due=12.5, i=99)  # never answered: waits 7.5 s
    late = log([21.0], due=19.0, i=98)  # answered after the close: 1.0 s
    early = log([10.1], due=9.0, i=97)  # due before the window: left out
    s = e2e.ttft_samples(answered + [waiting, late, early], W)
    assert len(s) == 11
    assert sorted(s)[-1] == 7.5 and sorted(s)[-2] == 1.0
    assert e2e.p95(s) > 1.0


def test_no_samples_reads_nothing():
    assert e2e.p95(np.array([])) is None
