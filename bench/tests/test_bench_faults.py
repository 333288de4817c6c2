"""A whole run on the CPU, past the harness's look for a chip, judges its
answers: sound, it is correct; with the served answers broken where the
device plan produces them, it is not."""
import jax
import pytest

from bench import run
from bench.tests.cells import PEAKS, SEED, tiny_cell
from repro.engine.device import CoaxDevicePlan


def _altered(collect):
    def faulty(self, ticket):        # one id of each wave off by one
        q, r, stats = collect(self, ticket)
        r = r.copy()
        if r.size:
            r[0] += 1
        return q, r, stats
    return faulty


def _half_left_out(collect):
    def faulty(self, ticket):        # the second half of each wave unanswered
        q, r, stats = collect(self, ticket)
        keep = q < (ticket["b"] + 1) // 2
        return q[keep], r[keep], stats
    return faulty


@pytest.mark.parametrize("workload", ["airline-80m.knn10-closed",
                                      "osm-105m.knn10-open"])
@pytest.mark.parametrize("fault", [None, _altered, _half_left_out])
def test_run_is_correct_only_with_sound_answers(workload, fault,
                                               monkeypatch):
    if fault is not None:
        monkeypatch.setattr(CoaxDevicePlan, "collect",
                            fault(CoaxDevicePlan.collect))
    lines = []
    out = run.run_cell(tiny_cell(workload), SEED, 0.3, False, PEAKS,
                       jax.devices(), log=lambda **kv: lines.append(kv),
                       kernel_check=False)
    assert out["correct"] is (fault is None)
    assert (out["failed"] > 0) is (fault is not None)
    assert list(out)[-1] == "checks" and out["attempted"] > 0
    assert set(out["metrics"]) == {m["name"] for m in
                                   tiny_cell(workload).end_to_end}
    assert [l["stage"] for l in lines] == ["setup", "window", "reference"]
