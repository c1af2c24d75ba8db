"""The train window's arithmetic."""

from harness.train import SyncWindow


def _drive(win, boundaries):
    return [win.boundary(t, s, 0.5) for t, s in boundaries]


def test_window_opens_and_closes_on_boundaries_only():
    win = SyncWindow(seconds=10.0, warm_steps=4, log_every=4)
    edges = _drive(win, [(1.0, 2), (5.0, 4), (9.0, 8), (13.0, 12), (17.0, 16), (21.0, 20)])
    assert edges == [None, "open", None, None, "close", None]
    assert win.open == (5.0, 4) and win.close == (17.0, 16)
    assert win.steps == 12
    assert win.rate(100, 1) == 12 * 100 / 12.0


def test_a_partial_step_is_never_counted():
    # --seconds runs out at t = 15, between the boundaries at 13 and 17:
    # the window closes at 17 with the whole steps up to there, not at 15.
    win = SyncWindow(seconds=10.0, warm_steps=4, log_every=4)
    _drive(win, [(5.0, 4), (9.0, 8), (13.0, 12), (17.0, 16)])
    assert win.close == (17.0, 16)
    assert win.steps % 4 == 0


def test_a_stall_inside_the_window_lowers_the_rate():
    steady = SyncWindow(10.0, 4, 4)
    _drive(steady, [(5.0, 4), (9.0, 8), (13.0, 12), (17.0, 16)])
    stalled = SyncWindow(10.0, 4, 4)
    _drive(stalled, [(5.0, 4), (9.0, 8), (16.0, 12)])  # 3 s of stall before the third boundary
    assert stalled.rate(100, 1) < steady.rate(100, 1)
    assert stalled.rate(100, 1) == 8 * 100 / 11.0


def test_rate_is_per_chip_and_nonfinite_losses_fail_their_steps():
    win = SyncWindow(1.0, 4, 4)
    win.boundary(0.0, 4, 0.5)
    win.boundary(0.5, 8, float("nan"))
    win.boundary(1.0, 12, 0.5)
    assert win.bad_steps == 4 and win.steps == 8
    assert win.rate(100, 4) == 8 * 100 / 1.0 / 4
