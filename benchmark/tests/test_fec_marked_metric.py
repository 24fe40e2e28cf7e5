"""``pump.fec_marked_pct.lossy``: the share of the streams the pump's
step loop was handed that the reliability tier asked for itself.  It is
appended after every entry the lossy cell was accepted with, names that
cell alone, reads ``fec_tier_marks_total`` over
``pump_stepped_streams_total`` and is silent on a program without the
family.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import pytest

import file_checks
from file_checks import BENCH, METRIC_DIR, load
from file_checks import LOSSY as CELL, LOSSY_OWN as OWN, LOSSY_TWINS as TWINS

from benchmark import readers

MARKED = "pump.fec_marked_pct.lossy"


def test_the_tiers_own_marks_follow_the_accepted_entries():
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(MARKED)
    before = set(names[:at])
    assert set(file_checks.ACCEPTED) | set(OWN) <= before
    assert {"delay_p95_ms.lossy"} | {f"{n}.lossy" for n in TWINS} <= before
    assert names.count(MARKED) == 1
    assert file_checks.entry(BENCH, MARKED) == {
        "name": MARKED, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "Pump",
        "moves": "delay_p60_ms", "workloads": [CELL]}


def test_the_reader_is_marks_over_stepped_streams_and_silent_without():
    spec = load(f"{METRIC_DIR}{MARKED}.json")
    assert spec["name"] == MARKED and spec["what"]
    assert spec["reader"] == {"kind": "ratio_of_deltas",
                              "num": "fec_tier_marks_total",
                              "den": "pump_stepped_streams_total",
                              "scale": 100.0}
    assert readers.read(spec, file_checks.silent_ctx(
        m0={"pump_stepped_streams_total": 100.0},
        m1={"pump_stepped_streams_total": 900.0})) is None
    m0 = {"pump_stepped_streams_total": 100.0,
          'fec_tier_marks_total{reason="budget"}': 0.0,
          'fec_tier_marks_total{reason="load"}': 2.0,
          'fec_tier_marks_total{reason="cursor"}': 0.0,
          'fec_tier_marks_total{reason="rung"}': 5.0}
    m1 = dict(m0, pump_stepped_streams_total=900.0)
    m1['fec_tier_marks_total{reason="budget"}'] = 3.0
    m1['fec_tier_marks_total{reason="rung"}'] = 25.0
    assert readers.read(spec, file_checks.silent_ctx(m0=m0, m1=m1)) == \
        pytest.approx(100 * 23 / 800)
