"""The readers of the planner call's own spans, on inputs worked out by
hand: 4 eager ticks and each span name's durations, in the form
``bench/drivers/eager_tick.py`` hands them over."""
import pytest

from bench.harness import metric_reader

INPUTS = {
    "ticks": 4,
    "spans": {"scenarios": [0.001, 0.003], "plan.prepare": [0.002],
              "plan.dispatch": [0.004], "plan.wait": [0.006],
              "plan.fetch": [0.001], "plan.decode": [0.002],
              "plan.price": [0.005], "plan.evaluate": [0.02]},
}
READINGS = {
    "scenarios_ms.tick": 1.0,            # (1 + 3) ms / 4 ticks
    "plan_prepare_ms.tick": 0.5,
    "plan_dispatch_ms.tick": 1.0,
    "plan_wait_ms.tick": 1.5,
    "plan_decode_ms.tick": 2.0,          # fetch + decode + price
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_span_reader(name):
    read = metric_reader(name)
    assert read(INPUTS) == pytest.approx(READINGS[name])
    # a program without these spans (the parent's: one plan.evaluate),
    # or an untraced run: no reading, and no error
    assert read({"ticks": 4, "spans": {"plan.evaluate": [0.02]}}) is None
    assert read({"ticks": 0, "spans": INPUTS["spans"]}) is None
    assert read({}) is None
