"""ISSUE 39: a ``jax.build`` says which program it was, from where, and
all three parts — each second once.

The three ``jax.monitoring`` listeners of ``easydarwin_tpu.device`` are
fed a recorded sequence (the order JAX 0.9.0 fires them in on a first
call: a start mark where a timed part begins, its seconds where it
ends, a cache-hit event before the backend part's end) and must file
one span and one event a backend event, with the parts charged once.
"""

import importlib.util
import json
import os
import threading

import pytest

from easydarwin_tpu import device, obs
from easydarwin_tpu.obs import TRACER, SpanTracer

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"

#: ("S" start mark | "D" its seconds | "E" plain event, event[, seconds],
#: fun_name) in the order JAX fires them
NESTED = [                      # outer calls inner twice: inlined, no build
    ("S", TRACE, "outer"),
    ("S", TRACE, "inner"), ("D", TRACE, 0.0025, "inner"),
    ("S", TRACE, "_reduce_sum"), ("D", TRACE, 0.0008, "_reduce_sum"),
    ("S", TRACE, "inner"), ("D", TRACE, 0.0, "inner"),
    ("D", TRACE, 0.0062, "outer"),
    ("S", LOWER, "jit(outer)"), ("D", LOWER, 0.0093, "jit(outer)"),
    ("S", BACKEND, "jit(outer)"), ("E", HIT),
    ("D", BACKEND, 0.0036, "jit(outer)")]
EAGER = [                       # jnp.zeros: a build of its own, compiled
    ("S", TRACE, "convert_element_type"),
    ("D", TRACE, 0.0004, "convert_element_type"),
    ("S", LOWER, "jit(convert_element_type)"),
    ("D", LOWER, 0.0048, "jit(convert_element_type)"),
    ("S", BACKEND, "jit(convert_element_type)"),
    ("D", BACKEND, 0.018, "jit(convert_element_type)")]
INSIDE = [                      # an eager helper met while f is traced
    ("S", TRACE, "f"),
    ("S", TRACE, "g"), ("D", TRACE, 0.001, "g"),
    ("S", TRACE, "helper"), ("D", TRACE, 0.0005, "helper"),
    ("S", LOWER, "jit(helper)"), ("D", LOWER, 0.002, "jit(helper)"),
    ("S", BACKEND, "jit(helper)"), ("D", BACKEND, 0.01, "jit(helper)"),
    ("D", TRACE, 0.02, "f"),
    ("S", LOWER, "jit(f)"), ("D", LOWER, 0.004, "jit(f)"),
    ("S", BACKEND, "jit(f)"), ("D", BACKEND, 0.03, "jit(f)")]


def feed(seq):
    for kind, event, *rest in seq:
        if kind == "S":
            device._on_start(event, 1.7e9, fun_name=rest[0])
        elif kind == "D":
            device._on_duration(event, rest[0], fun_name=rest[1])
        else:
            device._on_event(event)


def counters():
    return (obs.JAX_EXECUTABLES_BUILT.total(), obs.JAX_CACHE_HITS.total(),
            {p: obs.JAX_EXECUTABLE_BUILD_SECONDS.value(phase=p)
             for p in ("trace", "lower", "backend")})


@pytest.fixture(autouse=True)
def empty_ring():
    """The ring is the process's: one an earlier test filled to its
    16,384 places would drop what these tests file from its other end."""
    TRACER.clear()


def filed_since(seq0):
    spans = [(name, t0, dur, args) for name, _c, t0, dur, _t, args
             in TRACER.records() if name == "jax.build"]
    events = [e for e in obs.EVENTS.tail(since=seq0)
              if e["event"] == "jax.build"]
    return spans, events


#: name -> (sequence, the seconds of its outermost parts, and what it
#: files: program, source, trace_us, lower_us, backend_us)
CASES = {
    "nested": (NESTED, 0.0062 + 0.0093 + 0.0036,
               [("jit(outer)", "cache", 6200, 9300, 3600)]),
    "eager": (EAGER, 0.0004 + 0.0048 + 0.018,
              [("jit(convert_element_type)", "compile", 400, 4800, 18000)]),
    # the helper takes what ended before it (g's trace among it), f what
    # is left of its own trace: 20 ms less the 13.5 ms that ended inside
    "inside_a_trace": (INSIDE, 0.02 + 0.004 + 0.03,
                       [("jit(helper)", "compile", 1500, 2000, 10000),
                        ("jit(f)", "compile", 6500, 4000, 30000)])}
CASES["one_after_another"] = (
    NESTED + EAGER + INSIDE, sum(c[1] for c in CASES.values()),
    [filed for c in CASES.values() for filed in c[2]])


@pytest.mark.parametrize("seq, seconds, want", CASES.values(),
                         ids=list(CASES))
def test_one_build_a_backend_event_each_second_once(seq, seconds, want):
    built0, hits0, parts0 = counters()
    seq0 = obs.EVENTS.seq
    feed(seq)
    built1, hits1, parts1 = counters()
    spans, events = filed_since(seq0)
    n_backend = sum(k == "D" and e == BACKEND for k, e, *_ in seq)
    # the two label-less counters read what they read before
    assert built1 - built0 == n_backend == len(spans) == len(events)
    assert hits1 - hits0 == sum(k == "E" for k, *_ in seq)
    assert sum(e["source"] == "cache" for e in events) == hits1 - hits0
    # no second twice: the outermost parts' seconds are all there is
    grew = {p: parts1[p] - parts0[p] for p in parts1}
    assert sum(grew.values()) == pytest.approx(seconds)
    # the phase counter's children are the spans' parts, summed
    for p in grew:
        assert grew[p] * 1e6 == pytest.approx(
            sum(a[f"{p}_us"] for *_x, a in spans), abs=len(spans))
    for (_n, _t0, dur, args), ev in zip(spans, events):
        assert {"program", "source", "trace_us", "lower_us",
                "backend_us"} <= set(args)
        assert "invalid" not in ev and ev["wake"] is None
        assert (ev["program"], ev["source"]) == (args["program"],
                                                 args["source"])
        assert ev["seconds"] * 1e6 == pytest.approx(
            args["trace_us"] + args["lower_us"] + args["backend_us"], abs=2)
        assert dur >= 0
    assert [(a["program"], a["source"], a["trace_us"], a["lower_us"],
             a["backend_us"]) for *_x, a in spans] == want


def test_a_build_inside_a_wake_carries_its_number():
    seq0 = obs.EVENTS.seq
    TRACER.wake = 41
    try:
        feed(EAGER)
    finally:
        TRACER.wake = None
    feed(EAGER)
    spans, events = filed_since(seq0)
    assert [a.get("wake") for *_x, a in spans] == [41, None]
    assert [e["wake"] for e in events] == [41, None]


def test_add_stamps_the_wake_as_open_does():
    t = SpanTracer(capacity=8)
    t.add("engine.plan", 0, 5, outputs=3)
    t.wake = 7
    t.add("engine.plan", 0, 5, outputs=3)
    tok = t.open("engine.step")
    t.close(tok)
    t.wake = None
    t.add("native.egress", 0, 5)
    assert [r[5] for r in t.records()] == [
        {"outputs": 3}, {"outputs": 3, "wake": 7}, {"wake": 7}, None]


def test_a_threads_first_calls_are_its_own():
    """A trace left open on this thread takes nothing from, and gives
    nothing to, a build another thread makes meanwhile."""
    device._on_start(TRACE, 1.7e9, fun_name="slow")
    worker = threading.Thread(target=feed, args=(EAGER,))
    worker.start()
    worker.join(10)
    assert not worker.is_alive()
    device._on_duration(TRACE, 0.5, fun_name="slow")
    feed([("S", LOWER, "jit(slow)"), ("D", LOWER, 0.25, "jit(slow)"),
          ("S", BACKEND, "jit(slow)"), ("D", BACKEND, 1.0, "jit(slow)")])
    spans = [a for name, *_x, a in TRACER.records()
             if name == "jax.build"]
    assert [(a["program"], a["trace_us"], a["lower_us"], a["backend_us"])
            for a in spans] == [
        ("jit(convert_element_type)", 400, 4800, 18000),
        ("jit(slow)", 500000, 250000, 1000000)]


def test_a_real_first_call_is_named():
    """The installed JAX hands the listeners what the recorded sequence
    says it does: a jitted function met for the first time leaves one
    ``jax.build`` under its own name, its span from the start of its
    trace to the end of its backend part."""
    import jax
    import jax.numpy as jnp

    device.listen_builds()
    x = jnp.arange(12.0)                    # its helpers build first

    @jax.jit
    def issue39_named_step(v):
        return (v * 3.0).sum()

    TRACER.clear()
    built0, seq0 = counters()[0], obs.EVENTS.seq
    issue39_named_step(x).block_until_ready()
    issue39_named_step(x).block_until_ready()     # met before: no build
    spans, events = filed_since(seq0)
    assert counters()[0] - built0 == len(spans) == len(events) >= 1
    name, _t0, dur, args = spans[-1]
    assert args["program"] == "jit(issue39_named_step)"
    assert args["trace_us"] > 0 and args["lower_us"] > 0 \
        and args["backend_us"] > 0
    # the span holds its three parts and what lay between them
    assert dur / 1e3 >= args["trace_us"] + args["lower_us"] \
        + args["backend_us"] - 3


def test_span_breakdown_lists_a_rings_boot_and_builds(tmp_path, capsys):
    """``tools/span_breakdown.py`` on a ring dump: the boot phases in
    order, and every build with the spans that hold it on its thread —
    not the sender thread's ``native.egress``, which is filed from the
    loop thread and lies beside what that thread does."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "span_breakdown", os.path.join(repo, "tools", "span_breakdown.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    t = SpanTracer(capacity=64)
    ms = 1_000_000
    t.add("boot.interpreter", -400 * ms, 200 * ms)
    t.add("boot.imports", -200 * ms, 200 * ms)
    t.add("boot.listen", 0, 90 * ms, devices=1)
    t.add("jax.build", 10 * ms, 30 * ms, program="jit(init)",
          source="compile", trace_us=5000, lower_us=5000, backend_us=18000)
    t.wake = 3
    t.add("pump.wake", 100 * ms, 100 * ms)
    t.add("pump.live_relay", 105 * ms, 90 * ms)
    t.add("native.egress", 100 * ms, 99 * ms)
    t.add("engine.step", 110 * ms, 60 * ms)
    t.add("engine.step", 175 * ms, 10 * ms)         # a later step
    t.add("jax.build", 120 * ms, 40 * ms, program="jit(device_ring_append)",
          source="cache", trace_us=13000, lower_us=17000, backend_us=9000)
    ring = tmp_path / "ring.json"
    doc = t.dump()
    for e in doc["traceEvents"]:        # the dump's origin is the tracer's
        e["ts"] += t._epoch_ns / 1000.0
    ring.write_text(json.dumps(doc))
    events = tool.ring_events(str(ring))
    assert [(p["name"], p["seconds"]) for p in tool.boot_phases(events)] == [
        ("boot.interpreter", 0.2), ("boot.imports", 0.2),
        ("boot.listen", 0.09)]
    first, second = tool.builds(events)
    assert first["program"] == "jit(init)" and first["chain"] == [
        "boot.listen"]
    assert second["chain"] == ["pump.wake", "pump.live_relay", "engine.step"]
    assert (second["source"], second["wake"], second["backend_us"]) == (
        "cache", 3, 9000)
    assert tool.ring_events("trace_dir_or.xplane.pb") == []
    assert tool.main(["span_breakdown", str(ring)]) == 0
    out = capsys.readouterr().out
    assert "boot: interpreter 0.200 s, imports 0.200 s, listen 0.090 s " \
        "(devices=1); in all 0.490 s" in out
    assert "2 builds, 1 of them loaded from the cache" in out
    assert "in pump.wake > pump.live_relay > engine.step" in out
