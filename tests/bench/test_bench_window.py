"""The window is made of whole scheduler steps: it starts at a step
boundary and ends at the first boundary ``--seconds`` or more later, and
every rate counts all the work of its steps over their wall time."""
import types

import bench_cells  # noqa: F401
from bench.serve import Step, measure
from bench.metrics import lane_occupancy, tok_s


class FakeLoop:
    """Steps of given durations on a fake clock starting at 100 s."""

    def __init__(self, durations, tokens):
        self.t = 100.0
        self.todo = list(zip(durations, tokens))
        self.steps = []

    def step(self):
        d, n = self.todo.pop(0)
        st = Step(self.t, self.t + d, n, 2)
        self.t += d
        self.steps.append(st)
        return st


def test_window_ends_at_the_first_step_boundary_past_the_seconds():
    loop = FakeLoop([0.25, 0.25, 0.25, 0.5, 4.0], [4, 4, 4, 4, 256])
    t1 = measure(loop.step, 100.0, 1.0)
    # 0.25 * 3 = 0.75 s is short of 1 s; the fourth step ends at 1.25 s
    assert t1 == 101.25 and len(loop.steps) == 4


def test_a_long_step_is_taken_whole():
    loop = FakeLoop([0.5, 4.0, 0.5], [4, 256, 4])
    t1 = measure(loop.step, 100.0, 1.0)
    assert t1 == 104.5 and len(loop.steps) == 2


def test_rates_take_all_the_work_over_all_the_time():
    loop = FakeLoop([0.5, 1.5, 0.5], [4, 256, 4])
    t1 = measure(loop.step, 100.0, 2.5)
    ctx = types.SimpleNamespace(steps=loop.steps, t0=100.0, t1=t1,
                                geo={"lanes": 4})
    assert tok_s.read(ctx) == (4 + 256 + 4) / 2.5
    assert lane_occupancy.read(ctx) == 50.0
