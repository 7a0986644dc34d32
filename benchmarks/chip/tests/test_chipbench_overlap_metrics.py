"""decode_overlap_share on made-up records: the mean of the serve.step
spans' in_flight tags over the steps that carry one; None from steps
without the tag and from a program without spans; and each serve cell's
traced smoke window reads a share above 0."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import chipbench_smoke as smoke  # noqa: E402
import harness  # noqa: E402
import peaks  # noqa: E402
from repro.core import trace  # noqa: E402
from spans import Spans  # noqa: E402

KIND = "TPU v5 lite"


def reader():
    return harness.load_module(BENCH / "layer_metrics"
                               / "decode_overlap_share.py",
                               "layer_metric_decode_overlap_share")


def _steps(t, tags):
    """One serve.step span a second on the host clock, tagged by `tags`,
    each inside a harness span of the benchmark's log."""
    clock = [0.0]
    t.clock = lambda: clock[0]
    for i, tg in enumerate(tags):
        clock[0] = 2.0 + i
        sid = t.begin("serve.step")
        trace.tag(sid, **tg)
        clock[0] = 2.5 + i
        t.end(sid)
    log = Spans()
    log.log = [("bench/engine.step", 1.9 + i, 2.6 + i)
               for i in range(len(tags))]
    return {"spans": log}


@pytest.fixture
def profile(monkeypatch):
    t = trace.Tracer()
    monkeypatch.setattr(trace, "_PROFILE", t)
    monkeypatch.setattr(trace, "_ACTIVE", None)
    return t


@pytest.mark.parametrize("flags, share", [
    ([0, 1, 1, None], 2 / 3),           # the step that dispatched nothing
    ([1, 1, 1, 1], 1.0),
    ([0, None, 0], 0.0),
])
def test_overlap_share_is_the_mean_of_the_tagged_steps(profile, flags,
                                                       share):
    ctx = _steps(profile, [{} if f is None else {"in_flight": f}
                           for f in flags])
    assert reader().read(ctx, None) == pytest.approx(share)


def test_overlap_share_reads_none_from_untagged_steps(profile):
    ctx = _steps(profile, [{"kv_blocks": 3, "pool_blocks": 8}, {}])
    assert reader().read(ctx, None) is None


def test_overlap_share_reads_none_from_a_program_without_spans(monkeypatch):
    monkeypatch.setattr(trace, "_PROFILE", None)
    monkeypatch.setattr(trace, "_ACTIVE", None)
    log = Spans()
    log.log = [("bench/engine.step", 1.0, 2.0)]
    assert reader().read({"spans": log}, None) is None


@pytest.mark.parametrize("name", ["serve.qwen3_8b.chat",
                                  "serve.qwen3_8b.longgen",
                                  "serve.granite4h_small.reasoning"])
def test_traced_serve_smoke_window_reads_its_overlap_share(monkeypatch,
                                                           name):
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS[KIND]))
    out, line = smoke.run(name, trace=True)
    assert line["correct"] is True
    share = line["metrics"][
        f"decode_overlap_share.{name.split('.')[-1]}"]["value"]
    assert 0 < share <= 1
