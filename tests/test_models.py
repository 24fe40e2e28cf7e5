"""Flagship pipelines: relay (both parse backends/modes) + transcode ladder."""

import numpy as np

from easydarwin_tpu.models import RelayPipeline, TranscodePipeline
from easydarwin_tpu.models.relay_pipeline import RelayPipelineConfig
from easydarwin_tpu.models.transcode_pipeline import TranscodeConfig
from easydarwin_tpu.ops import transform as tf


def test_relay_pipeline_modes_agree():
    base = RelayPipeline(RelayPipelineConfig(window=64, subscribers=16))
    args = base.example_args()
    aff = base(*args)
    hdr_pipe = RelayPipeline(RelayPipelineConfig(window=64, subscribers=16,
                                                 mode="headers"))
    hdr = hdr_pipe(*args)
    # render affine params on host and compare to device-rendered headers
    from easydarwin_tpu.relay.fanout import render_headers
    prefix = args[0]
    host = render_headers(np.asarray(prefix[:, :2]),
                          np.asarray(aff["seq"]),
                          np.asarray(aff["timestamp"]),
                          np.asarray(aff["seq_off"]),
                          np.asarray(aff["ts_off"]), np.asarray(aff["ssrc"]))
    np.testing.assert_array_equal(host, np.asarray(hdr["headers"]))
    assert int(aff["newest_keyframe"]) == int(hdr["newest_keyframe"])


def test_relay_pipeline_spans_carry_trace_id():
    """pipeline.step spans carry the session correlation key — per-call
    trace_id= wins over the stamped default, absent means uncorrelated."""
    from easydarwin_tpu.obs import TRACER
    pipe = RelayPipeline(RelayPipelineConfig(window=64, subscribers=8))
    args = pipe.example_args()
    pipe(*args)
    pipe.trace_id = "sess-default"
    pipe(*args)
    pipe(*args, trace_id="sess-override")
    tids = [(a or {}).get("trace_id")
            for name, _c, _t, _d, _tid, a in TRACER.records()
            if name == "pipeline.step"][-3:]
    assert tids == [None, "sess-default", "sess-override"]


def test_relay_pipeline_pallas_backend_matches(monkeypatch):
    cfg = RelayPipelineConfig(window=64, subscribers=8)
    a = RelayPipeline(cfg)
    args = a.example_args()
    ref = a(*args)
    # the kernel never interprets on its own: on the CPU backend the
    # test asks for the interpreter by name
    import functools
    from easydarwin_tpu.models import relay_pipeline as rp
    monkeypatch.setattr(rp, "parse_packets_pallas", functools.partial(
        rp.parse_packets_pallas, interpret=True))
    b = RelayPipeline(RelayPipelineConfig(window=64, subscribers=8,
                                          use_pallas_parse=True))
    out = b(*args)
    for k in ("seq", "timestamp", "keyframe_first", "newest_keyframe",
              "fast_start"):
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(ref[k]),
                                      err_msg=k)


def test_transcode_ladder_pipeline():
    pipe = TranscodePipeline(TranscodeConfig(qualities=(80, 50, 20),
                                             decode_pixels=True))
    (levels,) = pipe.example_args(n_blocks=128)
    out = pipe(levels)
    assert out["rungs"].shape == (3, 128, 64)
    nz = np.asarray(out["nonzeros"])
    assert nz[0] >= nz[1] >= nz[2] > 0
    assert out["pixels"].shape == (128, 64)
    # top rung at the source quality reproduces levels closely
    top = np.asarray(out["rungs"][0])
    src = np.asarray(levels)
    qt_in = tf.quality_table(90)
    qt80 = tf.quality_table(80)
    manual = np.asarray(tf.requantize(levels, qt_in, qt80))
    # vmap+jit fusion may round differently at exact .5 boundaries
    diff = np.abs(top - manual)
    assert diff.max() <= 1
    assert (diff > 0).mean() < 0.02
