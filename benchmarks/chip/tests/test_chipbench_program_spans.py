"""program_spans.py on made-up records: the program's spans kept inside the
harness's window, moved onto the trace's clock by the harness spans that
both records hold, the device's idle stretches given to the innermost span
open over them, and the per-layer metrics read from them; and each serve
cell's traced smoke window on the CPU, whose program-span metrics all
read."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import chipbench_smoke as smoke  # noqa: E402
import harness  # noqa: E402
import program_spans as ps  # noqa: E402
import trace_reduce as tr  # noqa: E402
from repro.core import trace  # noqa: E402
from spans import Spans  # noqa: E402

OFFSET = 1000.0                 # trace clock less the host clock
READERS = ["ckpt_fetch_s", "ckpt_append_s", "ckpt_gc_s", "ckpt_manifest_ms",
           "ckpt_place_s", "queue_wait_p95_s", "admit_host_ms",
           "prefill_pad_share", "idle_step_ms", "idle_compact_ms"]


def reader(name):
    return harness.load_module(BENCH / "layer_metrics" / f"{name}.py",
                               f"layer_metric_{name}")


class _Clock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def _spans(t, clock, plan):
    """plan: (time, name or None, tags); a name opens a span, None closes the
    innermost."""
    sids = []
    for at, name, tags in plan:
        clock.now = at
        if name is None:
            t.end(sids.pop())
        else:
            sids.append(t.begin(name, **tags))


def _harness_log(entries, jitter=()):
    """The harness's Spans log (host clock) and the same spans as the trace
    holds them, OFFSET later, each start moved by its jitter."""
    log = Spans()
    log.log = list(entries)
    jit = list(jitter) + [0.0] * len(entries)
    events = [tr.Event(n, s + OFFSET + j, e + OFFSET)
              for (n, s, e), j in zip(entries, jit)]
    return log, events


def test_clock_offsets_pair_harness_spans_by_name_and_order():
    log, events = _harness_log([("bench/a", 1.0, 2.0), ("bench/b", 1.5, 1.7),
                                ("bench/a", 3.0, 4.0)],
                               jitter=[2e-6, -1e-6, 0.0])
    red = tr.Reduced(OFFSET, OFFSET + 10, [[]], [[]],
                     [tr.Event(tr.WINDOW_SPAN, OFFSET, OFFSET + 10)] + events)
    offsets = sorted(ps.clock_offsets(log.log, red))
    assert offsets == pytest.approx([OFFSET - 1e-6, OFFSET, OFFSET + 2e-6])


def test_idle_goes_to_the_innermost_open_span():
    labelled = [(0.0, 10.0, "serve.step"), (2.0, 5.0, "serve.admit"),
                (12.0, 15.0, ps.WAIT)]
    gaps = [(1.0, 3.0), (4.0, 6.0), (9.0, 13.0), (15.0, 16.0)]
    idle = ps.attribute_idle(gaps, labelled)
    assert idle == pytest.approx({"serve.step": 3.0, "serve.admit": 2.0,
                                  ps.WAIT: 1.0, ps.UNCOVERED: 3.0})
    assert sum(idle.values()) == pytest.approx(sum(b - a for a, b in gaps))
    assert ps.attribute_idle([(0.0, 1.0)], []) == {ps.UNCOVERED: 1.0}


@pytest.fixture
def profile(monkeypatch):
    """A profile tracer on a clock the test sets, standing in for the one a
    profiler session makes."""
    clock = _Clock()
    t = trace.Tracer(clock=clock)
    monkeypatch.setattr(trace, "_PROFILE", t)
    return t, clock


def test_serve_metrics_read_the_window_on_the_trace_clock(profile):
    t, clock = profile
    _spans(t, clock, [(0.5, "serve.step", {}), (0.6, None, {})])  # before
    t.record("serve.queued", 1.0, 2.0)                         # before
    t.record("serve.queued", 1.9, 2.0)
    t.record("serve.queued", 1.9, 2.5)
    _spans(t, clock, [
        (2.0, "serve.step", {}),
        (2.0, "serve.admit", {"prompt_tokens": 10, "padded_tokens": 40}),
        (2.25, None, {}),
        (2.5, "serve.admit", {"prompt_tokens": 30, "padded_tokens": 40}),
        (2.75, None, {}), (3.0, None, {}),
        (4.0, "serve.step", {}), (4.5, None, {}),
        (5.0, "serve.compact", {}), (5.5, None, {})])
    log, events = _harness_log([("bench/engine.step", 1.9, 3.1),
                                ("bench/engine.step", 3.9, 4.6),
                                ("bench/engine.compact", 4.9, 5.6),
                                ("bench/driver.wait_arrival", 6.0, 7.0)])
    ops = [tr.Event("op", OFFSET + a, OFFSET + b)
           for a, b in [(1.8, 2.1), (2.2, 2.8), (2.9, 4.2), (4.3, 5.1),
                        (5.4, 6.2), (6.9, 7.5)]]
    red = tr.Reduced(OFFSET + 1.8, OFFSET + 7.5, [ops], [ops],
                     [tr.Event(tr.WINDOW_SPAN, OFFSET + 1.8, OFFSET + 7.5)]
                     + events)
    ctx = {"spans": log}
    got = ps.read(ctx, red)
    assert got.pairs == 4 and got.offset == pytest.approx(OFFSET)
    assert got.offset_spread == pytest.approx(0.0, abs=1e-9)
    assert len(got.named("serve.step")) == 2        # those before are out
    assert len(got.named("serve.queued")) == 2
    # idle: 2.1-2.2 (admit), 2.8-2.9 (step), 4.2-4.3 (step), 5.1-5.4
    # (0.3 compact), 6.2-6.9 (0.7 wait)
    assert got.idle == pytest.approx({
        "serve.admit": 0.1, "serve.step": 0.2, "serve.compact": 0.3,
        ps.WAIT: 0.7, ps.UNCOVERED: 0.0}, abs=1e-9)
    assert got.idle_s == pytest.approx(1.3)
    m = {name: reader(name).read(ctx, red) for name in READERS}
    assert m["idle_step_ms"] == pytest.approx(100.0)
    assert m["idle_compact_ms"] == pytest.approx(300.0)
    assert m["admit_host_ms"] == pytest.approx(250.0)
    assert m["prefill_pad_share"] == pytest.approx(40 / 80)
    assert m["queue_wait_p95_s"] == pytest.approx(0.1 + 0.95 * 0.5)
    for name in ("ckpt_fetch_s", "ckpt_append_s", "ckpt_gc_s",
                 "ckpt_manifest_ms", "ckpt_place_s"):
        assert m[name] == 0.0                       # no save, no restore


def test_ckpt_metrics_read_per_save_and_leave_out_gc_manifests(profile):
    t, clock = profile
    plan = []
    for base in (1.0, 5.0):
        plan += [(base, "ckpt.save", {}), (base, "ckpt.save.fetch", {}),
                 (base + 0.2, None, {}),
                 (base + 0.2, "ckpt.store.append", {}), (base + 0.5, None, {}),
                 (base + 0.5, "ckpt.store.manifest", {}),
                 (base + 0.51, None, {}),
                 (base + 0.51, "ckpt.store.gc", {}),
                 (base + 1.0, "ckpt.store.manifest", {}),
                 (base + 1.1, None, {}), (base + 1.2, None, {}),
                 (base + 1.3, None, {}),
                 (base + 2.0, "ckpt.restore", {}),
                 (base + 2.3, "ckpt.restore.place", {}),
                 (base + 2.35, None, {}), (base + 2.4, None, {})]
    _spans(t, clock, plan)
    log, events = _harness_log([("bench/runner.run", 0.5, 3.5),
                                ("bench/runner.run", 4.5, 7.5)])
    ops = [tr.Event("op", OFFSET + 0.5, OFFSET + 7.5)]
    red = tr.Reduced(OFFSET + 0.5, OFFSET + 7.5, [ops], [ops], events)
    ctx = {"spans": log}
    m = {name: reader(name).read(ctx, red) for name in READERS}
    assert m["ckpt_fetch_s"] == pytest.approx(0.2)
    assert m["ckpt_append_s"] == pytest.approx(0.3)
    assert m["ckpt_gc_s"] == pytest.approx(0.69)
    assert m["ckpt_manifest_ms"] == pytest.approx(10.0)
    assert m["ckpt_place_s"] == pytest.approx(0.05)
    for name in ("queue_wait_p95_s", "admit_host_ms", "prefill_pad_share",
                 "idle_step_ms", "idle_compact_ms"):
        assert m[name] == 0.0                       # no serving work


def test_every_reader_reads_none_from_a_program_without_spans(monkeypatch):
    monkeypatch.setattr(trace, "_PROFILE", None)
    log, events = _harness_log([("bench/engine.step", 1.0, 2.0)])
    red = tr.Reduced(OFFSET, OFFSET + 3, [[]], [[]], events)
    for name in READERS:
        assert reader(name).read({"spans": log}, red) is None, name


@pytest.fixture
def cpu_peaks(monkeypatch):
    """The CPU has no published peaks: the v5e's stand in for the other
    readers' arithmetic (tests only)."""
    import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))


@pytest.mark.parametrize("name", [w["name"] for w in smoke.harness.
                                  load_benchmark()["workloads"]
                                  if w["name"].startswith("serve.")])
def test_traced_serve_smoke_window_reads_every_program_span_metric(
        cpu_peaks, name):
    out, line = smoke.run(name, trace=True)
    assert line["correct"] is True
    got = out.ctx["program_spans"]
    assert got.pairs > 0 and got.named("serve.step")
    assert got.named("serve.compact") and got.named("serve.admit")
    ours = [m["name"] for m in smoke.cell(name).per_layer
            if m["name"].split(".")[0] in READERS]
    assert ours and all(line["metrics"][m]["value"] >= 0 for m in ours)
    assert line["metrics"][f"idle_step_ms.{name.split('.')[-1]}"][
        "value"] > 0
