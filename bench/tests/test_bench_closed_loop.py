"""The closed loop's stall record, driven by a fake server on a fake clock:
how much of the window was stall, with ``answered_qps`` as before."""
import contextlib

import pytest

from bench import spec
from bench.tests.cells import ROOT

OUTSTANDING = 4
D = 1 / 32                    # a drain's time, exact in binary


class _Clock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


class _Server:
    """Answers every pending query in one drain that lasts the next of
    ``durations`` on the clock."""

    def __init__(self, clock, durations):
        self.clock, self.durations, self.pending, self.n = clock, durations, [], 0

    def __len__(self):
        return len(self.pending)

    def submit(self, rect):
        self.n += 1
        self.pending.append(self.n)
        return self.n

    def drain(self):
        self.clock.now += self.durations.pop(0)
        out = {q: q for q in self.pending}
        self.pending = []
        return out


@pytest.mark.parametrize("durations, seconds, stalls, stall_s", [
    ([D] * 10, 10 * D, 0, 0.0),                           # no stall
    ([D] * 5 + [10 * D] + [D] * 4, 19 * D, 1, 7 * D),     # one at 10x
    ([D] * 9 + [10 * D], 12 * D, 1, 7 * D),               # the window ends on it
    ([D] * 8 + [4 * D, 10 * D], 20 * D, 2, 8 * D),        # two, one just over
], ids=["no-stall", "one-at-10x", "ends-on-stall", "two-stalls"])
def test_stall_record(durations, seconds, stalls, stall_s):
    closed_loop = spec.load_module(ROOT, "traffic", "closed_loop")
    clock = _Clock()
    drains = len(durations)
    win = closed_loop.run(_Server(clock, list(durations)), list(range(8)),
                          list(range(8)), seconds,
                          {"outstanding": OUTSTANDING}, None,
                          lambda name: contextlib.nullcontext(), clock=clock)
    log = win["log"]
    assert log["drains"] == drains and log["elapsed_s"] == sum(durations)
    assert log["drain_s"]["median"] == D
    assert log["stalls"] == stalls and log["stall_s"] == stall_s
    assert win["metrics"] == {
        "answered_qps": drains * OUTSTANDING / sum(durations)}
