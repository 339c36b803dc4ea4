"""The end-to-end arithmetic of the serving readers: nearest-rank
medians and tails over the requests due in the window, a request with no
first token counted as a miss, TPOT over a request's own tokens, tokens
per second over the window's whole steps, and readers of split
quantities found by their base name."""
import types

import pytest

import bench_smoke  # noqa: F401
import harness
from drivers.serve import Record


def _run(records, *, drain=True, seconds=10.0, loop_end=25.0, tokens=0,
         window_s=10.0):
    tracker = types.SimpleNamespace(tokens_in_window=tokens)
    return harness.Run(setup_s=1.0, window_s=window_s, attempted=0,
                       failed=0, checks=[], memory_peak_bytes=0, peaks={},
                       data={"records": records, "drain": drain,
                             "seconds": seconds, "loop_end_s": loop_end,
                             "tracker": tracker})


def _rec(arrival, first, last, n, admitted=None):
    return Record(arrival=arrival, n_prompt=10, max_new=n,
                  admitted=admitted, t_first=first, t_last=last, n_tokens=n)


def test_nearest_rank():
    assert harness.nearest_rank(list(range(1, 101)), 95) == 95
    assert harness.nearest_rank([3.0, 1.0, 2.0], 50) == 2.0
    assert harness.nearest_rank([5.0], 95) == 5.0


def test_ttft_counts_a_request_without_first_token_as_a_miss():
    ttft = harness.metric_reader("ttft_p50_ms")
    done = [_rec(i * 0.1, i * 0.1 + 0.2, i * 0.1 + 1.0, 5)
            for i in range(9)]
    # nine answered in 200 ms and one (due at 5 s) never got a token: the
    # median of ten is the 5th value, 200 ms
    miss = _rec(5.0, None, None, 0)
    assert ttft.read(_run(done + [miss])) == pytest.approx(200.0)
    # with six misses it lands on one, read at the loop's end
    misses = [_rec(5.0 + i * 0.1, None, None, 0) for i in range(6)]
    got = ttft.read(_run(done[:4] + misses))
    assert got == pytest.approx((25.0 - 5.0 - 0.5) * 1e3)
    # requests of the pre-roll and those due after the window are not
    # the window's
    others = [_rec(-3.0, 30.0, 31.0, 2), _rec(11.0, 80.0, 81.0, 2)]
    assert ttft.read(_run(done + [miss] + others)) == pytest.approx(200.0)
    assert ttft.read(_run(done, drain=False)) is None


@pytest.mark.parametrize("q,want", [(50, 20.0), (90, 50.0)])
def test_tpot_is_per_request_and_skips_single_tokens(q, want):
    tpot = harness.metric_reader(f"tpot_p{q}_ms")
    recs = [_rec(0.0, 1.0, 1.0 + 0.02 * 9, 10),      # 20 ms a token
            _rec(0.5, 1.0, 1.0 + 0.01 * 9, 10),      # 10 ms a token
            _rec(0.0, 1.0, 1.0 + 0.05 * 4, 5),       # 50 ms a token
            _rec(0.0, 2.0, 2.0, 1),                  # one token: no gap
            _rec(-1.0, 0.5, 9.0, 3)]                 # the pre-roll's
    assert tpot.read(_run(recs)) == pytest.approx(want)
    assert tpot.read(_run([recs[3]])) is None


def test_output_rate_over_the_windows_whole_steps():
    rate = harness.metric_reader("output_tok_s")
    assert rate.read(_run([], tokens=1000, window_s=10.5)) == \
        pytest.approx(1000 / 10.5)


def test_queue_wait_median():
    qw = harness.metric_reader("queue_wait_p50_ms")
    recs = [_rec(1.0, 2.0, 3.0, 2, admitted=1.0 + w)
            for w in (0.01, 0.02, 0.5)]
    recs.append(_rec(-2.0, 2.0, 3.0, 2, admitted=1.0))
    assert qw.read(_run(recs)) == pytest.approx(20.0)


def test_split_quantities_are_read_by_their_base_reader():
    base = harness.metric_reader("device_idle_frac")
    for part in ("chat", "batch"):
        split = harness.metric_reader(f"device_idle_frac.{part}")
        assert split.__file__ == base.__file__
