"""PR 39's nine per-layer metrics — what a server did before its window,
read from its own counters: each file loads, names a reader that exists
and matches its ``BENCHMARK.json`` entry (``file_checks.check_metric``,
as for every entry); the new reader ``value_at`` reads a family, one
labelled child, the window's first scrape and its last, and gives
nothing on a program without the family.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import pytest

import file_checks
from file_checks import BENCH, COMMITTED, load, silent_ctx as ctx

from benchmark import readers
from benchmark.run import parse_metrics

ALL = [w["name"] for w in BENCH["workloads"]]
#: the cells ``compiles_in_window`` is read in, which the two window
#: metrics stand beside
JUDGED = file_checks.entry(BENCH, "compiles_in_window")["workloads"]
#: name -> (reader kind, layer, the end-to-end metric it moves, cells)
NINE = {
    "boot.total_s": ("value_at", "Server boot", "setup_s", ALL),
    "boot.imports_s": ("value_at", "Server boot", "setup_s", ALL),
    "boot.native_s": ("value_at", "Server boot", "setup_s", ALL),
    "boot.backend_s": ("value_at", "Server boot", "setup_s", ALL),
    "setup.build_s": ("value_at", "Device steps", "setup_s", ALL),
    "setup.builds": ("value_at", "Device steps", "setup_s", ALL),
    "rtsp.handler_s.setup": ("value_at", "RTSP front end", "setup_s", ALL),
    "loads_in_window": ("counter_delta", "Device steps", "delay_p95_ms",
                        JUDGED),
    "build_s_in_window": ("counter_delta", "Device steps", "delay_p95_ms",
                          JUDGED)}

#: a server's ``/metrics`` as the window opens (``m0``) and as it closes
#: (``m1``), cut to the families read here: it booted in 13.5 s, built 57
#: executables before the window, 49 of them loaded, and one more,
#: loaded, inside it
SCRAPE_M0 = """\
# HELP server_boot_seconds Seconds of this process's boot, by phase
# TYPE server_boot_seconds gauge
server_boot_seconds{phase="backend"} 6.25
server_boot_seconds{phase="imports"} 4.5
server_boot_seconds{phase="interpreter"} 0.5
server_boot_seconds{phase="listen"} 2
server_boot_seconds{phase="native"} 0.25
server_boot_seconds{phase="total"} 13.5
# TYPE jax_executables_built_total counter
jax_executables_built_total 57
jax_executable_build_seconds_total{phase="backend"} 6.5
jax_executable_build_seconds_total{phase="lower"} 2.25
jax_executable_build_seconds_total{phase="trace"} 1.25
jax_persistent_cache_hits_total 49
rtsp_request_seconds_total{method="announce"} 0.125
rtsp_request_seconds_total{method="play"} 2.5
rtsp_request_seconds_total{method="setup"} 1.375
rtsp_requests_total{method="play"} 4096
"""
SCRAPE_M1 = SCRAPE_M0.replace(
    "jax_executables_built_total 57", "jax_executables_built_total 58"
).replace(
    'seconds_total{phase="backend"} 6.5', 'seconds_total{phase="backend"} 7'
).replace(
    'seconds_total{phase="lower"} 2.25', 'seconds_total{phase="lower"} 2.5'
).replace("cache_hits_total 49", "cache_hits_total 50")
M0, M1 = parse_metrics(SCRAPE_M0), parse_metrics(SCRAPE_M1)


@pytest.mark.parametrize("name", NINE)
def test_the_nine_match_their_files_and_entries(name):
    kind, layer, moves, cells = NINE[name]
    entry = file_checks.entry(BENCH, name)
    file_checks.check_metric(BENCH, COMMITTED, entry)
    spec = load(f"benchmark/layer_metrics/{name}.json")
    assert spec["reader"]["kind"] == kind and len(spec["what"]) > 40
    assert entry["source"] == "program_counter"
    assert (entry["layer"], entry["moves"]) == (layer, moves)
    assert entry["workloads"][:len(cells)] == cells
    assert entry["better"] == "lower"
    # they come after every entry accepted before them
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(name) > names.index("egress.hidden_pct.below_knee")


def at(metric, where, **window):
    return readers.read({"reader": {"kind": "value_at", "metric": metric,
                                    "at": where}}, ctx(**window))


def test_value_at_reads_a_family_a_child_and_either_end():
    w = {"m0": M0, "m1": M1}
    # a family's children are summed, a labelled child read alone
    assert at("jax_executable_build_seconds_total", "m0", **w) == 10.0
    assert at("jax_executable_build_seconds_total", "m1", **w) == 10.75
    assert at('server_boot_seconds{phase="total"}', "m0", **w) == 13.5
    assert at('server_boot_seconds{phase="native"}', "m0", **w) == 0.25
    assert at("rtsp_request_seconds_total", "m0", **w) == 4.0
    assert at("jax_executables_built_total", "m0", **w) == 57
    assert at("jax_executables_built_total", "m1", **w) == 58
    # nothing to read: nothing, not 0
    assert at("server_boot_seconds", "m0") is None
    assert at('server_boot_seconds{phase="total"}', "m0",
              m0={"jax_executables_built_total": 3.0}) is None
    # a name that another name starts with is another family
    assert at("rtsp_request", "m0", **w) is None


def test_the_nine_on_the_recorded_scrape_and_on_a_program_without():
    def read(name, **window):
        return readers.read(load(f"benchmark/layer_metrics/{name}.json"),
                            ctx(**window))
    got = {name: read(name, m0=M0, m1=M1) for name in NINE}
    assert got == {
        "boot.total_s": 13.5, "boot.imports_s": 4.5, "boot.native_s": 0.25,
        "boot.backend_s": 6.25, "setup.build_s": 10.0, "setup.builds": 57,
        "rtsp.handler_s.setup": 4.0, "loads_in_window": 1.0,
        "build_s_in_window": 0.75}
    # the parent of PR 39 has the three jax_* counters without the label
    # and none of the new families: the boot and the handlers are silent
    old = {"jax_executables_built_total": 57.0,
           "jax_executable_build_seconds_total": 6.5,
           "jax_persistent_cache_hits_total": 49.0}
    silent = {name for name in NINE if read(name, m0=old, m1=old) is None}
    assert silent == {"boot.total_s", "boot.imports_s", "boot.native_s",
                      "boot.backend_s", "rtsp.handler_s.setup"}
    assert all(read(name) is None for name in NINE)
