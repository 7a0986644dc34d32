"""The decode step's per-layer metrics on made-up records: kv_read_share
from the serve.step spans' kv_blocks and pool_blocks tags, and
paged_attn_roofline from those tags and the paged-attention kernel's own
custom calls in jit_decode_fn (the token write's are left out); both read
None from a program without the tags or the kernel; and each serve cell's
traced smoke window reads its share."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import chipbench_smoke as smoke  # noqa: E402
import harness  # noqa: E402
import peaks  # noqa: E402
import trace_reduce as tr  # noqa: E402
from repro.core import trace  # noqa: E402
from spans import Spans  # noqa: E402

OFFSET = 1000.0                 # trace clock less the host clock
POOL = (9, 32, 32, 64, 8, 128)  # the serving cell's pool_k
KIND = "TPU v5 lite"


def reader(name):
    return harness.load_module(BENCH / "layer_metrics" / f"{name}.py",
                               f"layer_metric_{name}")


def _window(t, steps, kernel_s):
    """Two engine steps on the host clock, tagged by `steps`, and a trace
    holding kernel_s of paged-attention custom calls, a token write of 1 s
    and a prefill's custom call."""
    clock = [0.0]
    t.clock = lambda: clock[0]
    for i, tags in enumerate(steps):
        clock[0] = 2.0 + i
        sid = t.begin("serve.step")
        trace.tag(sid, **tags)
        clock[0] = 2.5 + i
        t.end(sid)
    log = Spans()
    log.log = [("bench/engine.step", 1.9 + i, 2.6 + i)
               for i in range(len(steps))]
    name = "jit_decode_fn:%{} = bf16[32,32,128] custom-call"
    ops = [tr.Event(name.format("paged_decode_attention.15"), OFFSET + 2.0,
                    OFFSET + 2.0 + kernel_s),
           tr.Event(name.format("paged_kv_write.15"), OFFSET + 3.0,
                    OFFSET + 4.0),
           tr.Event("jit_prefill_fn:%custom-call.3 = bf16[1] custom-call",
                    OFFSET + 4.0, OFFSET + 4.1)]
    spans = [tr.Event(n, s + OFFSET, e + OFFSET) for n, s, e in log.log]
    red = tr.Reduced(OFFSET + 1.8, OFFSET + 4.2, [ops], [ops],
                     [tr.Event(tr.WINDOW_SPAN, OFFSET + 1.8, OFFSET + 4.2)]
                     + spans)
    return {"spans": log, "pool_shape": POOL, "pool_itemsize": 2,
            "device_kind": KIND}, red


@pytest.fixture
def profile(monkeypatch):
    t = trace.Tracer()
    monkeypatch.setattr(trace, "_PROFILE", t)
    monkeypatch.setattr(trace, "_ACTIVE", None)
    return t


def test_decode_metrics_read_the_tags_and_the_kernel(profile):
    ctx, red = _window(profile, [{"kv_blocks": 300, "pool_blocks": 1024},
                                 {"kv_blocks": 212, "pool_blocks": 1024}],
                       kernel_s=0.002)
    assert reader("kv_read_share").read(ctx, red) == pytest.approx(
        512 / 2048)
    need = 2 * 9 * 512 * 64 * 8 * 128 * 2         # K and V, every layer
    bw = peaks.peaks_for(KIND)["hbm_bytes_per_s"]
    assert reader("paged_attn_roofline").read(ctx, red) == pytest.approx(
        100 * need / bw / 0.002)


@pytest.mark.parametrize("name", ["kv_read_share", "paged_attn_roofline"])
def test_decode_metrics_read_none_from_untagged_steps(profile, name):
    ctx, red = _window(profile, [{}, {}], kernel_s=0.002)
    assert reader(name).read(ctx, red) is None


def test_roofline_reads_none_without_the_kernel(profile):
    ctx, red = _window(profile, [{"kv_blocks": 1, "pool_blocks": 4}],
                       kernel_s=0.002)
    red.ops = [[e for e in red.ops[0] if "paged_decode" not in e.name]]
    assert reader("paged_attn_roofline").read(ctx, red) is None


def test_decode_metrics_read_none_from_a_program_without_spans(monkeypatch):
    monkeypatch.setattr(trace, "_PROFILE", None)
    monkeypatch.setattr(trace, "_ACTIVE", None)
    log = Spans()
    log.log = [("bench/engine.step", 1.0, 2.0)]
    red = tr.Reduced(OFFSET, OFFSET + 3, [[]], [[]],
                     [tr.Event("bench/engine.step", OFFSET + 1, OFFSET + 2)])
    for name in ("kv_read_share", "paged_attn_roofline"):
        assert reader(name).read({"spans": log}, red) is None, name


@pytest.mark.parametrize("name", ["serve.qwen3_8b.chat",
                                  "serve.qwen3_8b.longgen"])
def test_traced_serve_smoke_window_reads_its_kv_share(monkeypatch, name):
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS[KIND]))
    out, line = smoke.run(name, trace=True)
    assert line["correct"] is True
    share = line["metrics"][f"kv_read_share.{name.split('.')[-1]}"]["value"]
    assert 0 < share < 1
    # the CPU's engine serves with the jnp oracle: no kernel to time
    assert not any(k.startswith("paged_attn_roofline")
                   for k in line["metrics"])
