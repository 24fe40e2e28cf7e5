"""PR 25's per-layer metrics: each new file loads, names a reader that
exists and matches its ``BENCHMARK.json`` entry; the new reader gives
the known numbers on a small recorded window, and every one of them
nothing on a program that has none of the families.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import pytest

import file_checks
from file_checks import load, silent_ctx as ctx

from benchmark import readers

WINDOW = load("benchmark/tests/data/metrics_window.json")


@pytest.mark.parametrize("check", file_checks.params("spans"))
def test_pr25s_entries_stand_as_accepted(check):
    """Each of the fourteen matches its file and keeps its cells at the
    head of its list; they follow the nine before them, in order
    (file_checks.py: ``PR25``, ``OLD``)."""
    check()


def quantile(q, **window):
    return readers.read({"reader": {
        "kind": "histogram_quantile", "family": "relay_due_to_wire_seconds",
        "q": q, "scale": 1000.0}}, ctx(**window))


def test_histogram_quantile_on_the_recorded_window():
    # growth over the window, both engines summed: 40 in (0, 50 ms],
    # 30 in (50, 100], 20 in (100, 250], 8 in (250, 500], 100 in
    # (500, 1000], 2 beyond — 200 in all
    assert quantile(0.10, **WINDOW) == pytest.approx(25.0)     # 20 of 40
    assert quantile(0.20, **WINDOW) == pytest.approx(50.0)
    assert quantile(0.275, **WINDOW) == pytest.approx(75.0)    # 15 of 30
    assert quantile(0.95, **WINDOW) == pytest.approx(960.0)    # 92 of 100
    assert quantile(0.995, **WINDOW) == pytest.approx(1000.0)  # in +Inf
    # what was there before the window does not count
    assert quantile(0.5, m0=WINDOW["m1"], m1=WINDOW["m1"]) is None
    assert quantile(0.5) is None


def test_histogram_quantile_agrees_with_the_programs_own():
    from easydarwin_tpu.obs.metrics import Histogram
    h = Histogram("t_seconds", "t", labels=("engine",))
    for i in range(1, 400):
        h.observe(i * 0.0013, engine="native" if i % 3 else "scalar")
    m1 = {}
    for ln in h.expose_lines():
        key, _, val = ln.rpartition(" ")
        m1[key] = float(val)
    for q in (0.5, 0.95, 0.99):
        got = readers.read({"reader": {"kind": "histogram_quantile",
                                       "family": "t_seconds", "q": q}},
                           ctx(m1=m1))
        assert got == pytest.approx(h.quantile(q))


def test_ratio_of_deltas_over_one_labelled_child_and_the_family():
    spec = load("benchmark/layer_metrics/pump.busy_pct.below_knee.json")
    # wake grew 3 s of the 10 s the loop's two states grew together
    assert readers.read(spec, ctx(**WINDOW)) == pytest.approx(30.0)


def test_the_wake_decomposition_on_the_recorded_window():
    def read(name):
        return readers.read(load(f"benchmark/layer_metrics/{name}.json"),
                            ctx(**WINDOW))
    # 20 wakes in the window: 2.4 s of live_relay units, 0.3 s of
    # megabatch units, 1.6 s of egress brackets; 5 of them timer wakes
    assert read("pump.live_relay_ms_per_wake.below_knee") == \
        pytest.approx(120.0)
    assert read("pump.megabatch_ms_per_wake.above_knee") == \
        pytest.approx(15.0)
    assert read("egress.bracket_ms_per_wake.below_knee") == \
        pytest.approx(80.0)
    assert read("pump.timer_wakes_pct.below_knee") == pytest.approx(25.0)
