"""The readers of the end-to-end and host-clock metrics, on records
made by hand."""
import pytest

from bench import harness
from bench.harness import Req, Run, Tick


def read(name, run):
    return harness.load_reader(name)(run)


def run_of(kind, reqs, ticks=(), t0=0.0, t1=10.0):
    r = Run("w", {}, {"kind": kind}, "TPU v5 lite", 1)
    r.requests, r.ticks = list(reqs), list(ticks)
    r.window_t0, r.window_t1 = t0, t1
    return r


def test_ttft_is_over_every_request_from_its_scheduled_arrival():
    reqs = [Req(float(i), 8, 4, token_times=[i + 0.001 * (i + 1)])
            for i in range(100)]
    # the 95th percentile of 1..100 ms, interpolated linearly
    assert read("ttft_p95_ms", run_of("open_loop", reqs)) == \
        pytest.approx(95.05)
    assert read("ttft_p95_ms", run_of("closed_loop", reqs)) is None


def test_itl_is_over_every_gap_of_every_request():
    a = Req(0.0, 8, 4, token_times=[1.0, 1.01, 1.02, 1.03])     # 10 ms
    b = Req(0.0, 8, 3, token_times=[2.0, 2.1, 2.2])             # 100 ms
    got = read("itl_p95_ms", run_of("open_loop", [a, b]))
    # five gaps: 10, 10, 10, 100, 100 -> p95 interpolates to 100
    assert got == pytest.approx(100.0)


def test_closed_loop_itl_keeps_the_gaps_inside_the_window():
    a = Req(0.0, 8, 4, token_times=[0.5, 1.5, 1.6, 11.0])
    got = read("itl_p95_ms", run_of("closed_loop", [a], t0=1.0, t1=10.0))
    assert got == pytest.approx(100.0)


def test_tokens_per_s_is_over_the_whole_window():
    a = Req(0.0, 8, 5, token_times=[0.5, 2.0, 4.0, 6.0, 10.5])
    r = run_of("closed_loop", [a], [Tick(1.0, 2.0, [], 1, 9)],
               t0=1.0, t1=5.0)
    assert read("tokens_per_s", r) == pytest.approx(2 / 4.0)


def test_msg_rate_counts_every_rank_and_lane():
    r = run_of("ring_rounds", [], t0=0.0, t1=2.0)
    r.rounds, r.messages_per_round = 1000, 256
    assert read("msg_rate", r) == pytest.approx(128000.0)


def test_tick_ms_averages_the_traced_ticks():
    ticks = [Tick(0.0, 0.010, [], 1, 1, traced=True),
             Tick(0.010, 0.030, [], 1, 1, traced=True),
             Tick(0.030, 1.030, [], 1, 1, traced=False)]
    assert read("tick_ms", run_of("open_loop", [], ticks)) == \
        pytest.approx(15.0)


def test_setup_s_is_the_recorded_set_up():
    r = run_of("open_loop", [])
    r.setup_s = 12.5
    assert read("setup_s", r) == 12.5


def test_percentile_of_nothing_is_nothing():
    assert harness.percentile([], 95) is None
    assert read("ttft_p95_ms", run_of("open_loop", [])) is None
