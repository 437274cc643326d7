"""step_ms is the window's wall time over the whole steps it completed."""

import numpy as np

from bench.paths.step import timed_window


class FakeDevice:
    """Steps of ``step_s`` each, run back to back in dispatch order."""

    def __init__(self, step_s):
        self.now = 0.0
        self.step_s = step_s
        self.done_at = []

    def clock(self):
        return self.now

    def dispatch(self, i):
        start = max(self.now, self.done_at[-1] if self.done_at else 0.0)
        self.done_at.append(start + self.step_s)
        return i

    def wait(self, i):
        self.now = max(self.now, self.done_at[i])


def test_window_counts_whole_steps_and_waits_for_the_last():
    dev = FakeDevice(7.0)
    n, elapsed = timed_window(dev.dispatch, dev.wait, 10.0, dev.clock)
    # step 0 ends at 7 (< 10): step 2 is queued; step 1 ends at 14: stop,
    # and the queued step 2 ends at 21
    assert (n, elapsed) == (3, 21.0)
    assert elapsed / n == 7.0


def test_zero_second_window_takes_two_steps():
    dev = FakeDevice(0.25)
    n, elapsed = timed_window(dev.dispatch, dev.wait, 0.0, dev.clock)
    assert (n, elapsed) == (2, 0.5)


def test_short_steps_fill_the_window():
    dev = FakeDevice(0.1)
    n, elapsed = timed_window(dev.dispatch, dev.wait, 10.0, dev.clock)
    assert elapsed >= 10.0 and elapsed - 10.0 <= 0.2 + 1e-9
    assert abs(elapsed / n - 0.1) < 1e-9


def test_no_window_step_repeats_a_batch():
    from bench.tests.test_bench_reference import tiny_cell
    from bench.traffic_gen import key_of
    cell = tiny_cell("bfloat16")
    ring = [np.asarray(b["gidx"]) for b in cell.ring(11)]
    checked = [np.asarray(cell.make_batch(key_of(11, "batch", t))["gidx"])
               for t in range(cell.traffic["checked_steps"])]
    dev = FakeDevice(7.0)
    n, _ = timed_window(dev.dispatch, dev.wait, 10.0, dev.clock)
    assert len(ring) >= n
    every = ring + checked
    assert not any(np.array_equal(every[i], every[j])
                   for i in range(len(every)) for j in range(i))
