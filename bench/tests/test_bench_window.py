"""Window arithmetic: rates over the whole window, tails over every
sample in it."""
import numpy as np

from bench import window


def _steady(n_req=16, ticks=200, dt=0.02, stall=None):
    """Every request emits one token per tick; ``stall`` = (first tick,
    n ticks, seconds each) slows a stretch of ticks."""
    t, times = 0.0, []
    for k in range(ticks):
        step = dt
        if stall and stall[0] <= k < stall[0] + stall[1]:
            step = stall[2]
        t += step
        times.append(t)
    tok = {r: list(times) for r in range(n_req)}
    due = {r: 0.0 for r in range(n_req)}
    return tok, due


def test_rate_is_all_tokens_over_all_time():
    tok, due = _steady()
    w = window.window_stats(tok, due, 0.0, 4.0 + 1e-9)
    assert w["tokens"] == 16 * 200
    assert abs(w["tokens_per_s"] - 16 * 200 / 4.0) < 1e-6


def test_stall_inside_the_window_moves_rate_and_tail():
    tok, due = _steady()
    base = window.window_stats(tok, due, 0.0, 4.0 + 1e-9)
    tok2, due2 = _steady(stall=(50, 20, 0.2))     # 10% of ticks 10x slower
    t_close = 4.0 + 20 * (0.2 - 0.02) + 1e-9
    slow = window.window_stats(tok2, due2, 0.0, t_close)
    assert slow["tokens"] == base["tokens"]
    assert slow["tokens_per_s"] < 0.6 * base["tokens_per_s"]
    assert slow["itl_p95_ms"] > 5 * base["itl_p95_ms"]


def test_samples_outside_the_window_do_not_count():
    tok = {0: [0.5, 1.5, 2.5], 1: [2.2, 2.4]}
    due = {0: 0.0, 1: 2.0}
    w = window.window_stats(tok, due, 1.0, 2.45)
    assert w["tokens"] == 3                       # 1.5, 2.2, 2.4
    assert w["ttft_n"] == 1                       # request 1 only
    assert abs(w["ttft_p50_ms"] - 200.0) < 1e-6
    assert w["itl_n"] == 2                        # 0.5->1.5, 2.2->2.4


def test_percentile_is_exact():
    v = list(range(1, 101))
    assert window.percentile(v, 95) == float(np.percentile(v, 95))
    assert window.percentile([], 95) is None
