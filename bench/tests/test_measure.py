"""The window rules of the end-to-end metrics."""
import pytest

from measure import run_window, sweep_ms


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_window_finishes_the_chunk_in_flight():
    clock = FakeClock()

    def chunk():
        clock.t += 4.0  # a chunk of 5 sweeps takes 4 s
        return 5

    finished = []
    units, t0, t_end = run_window(10.0, chunk, lambda: finished.append(1),
                                  clock=clock)
    # 4, 8 and 12 s: the third chunk crosses the deadline and is counted
    assert units == 15 and t_end - t0 == 12.0 and finished == [1]
    assert sweep_ms(units, t0, t_end) == pytest.approx(800.0)


def test_window_counts_time_spent_finishing():
    clock = FakeClock()

    def chunk():
        clock.t += 1.0
        return 1

    def finish():
        clock.t += 0.5

    units, t0, t_end = run_window(2.0, chunk, finish, clock=clock)
    assert units == 2 and t_end - t0 == 2.5
    assert sweep_ms(units, t0, t_end) == pytest.approx(1250.0)


def test_sweep_ms_needs_a_sweep():
    with pytest.raises(ValueError):
        sweep_ms(0, 0.0, 1.0)
