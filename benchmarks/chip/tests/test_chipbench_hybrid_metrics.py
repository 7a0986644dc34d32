"""The Granite-4.0-H cell's own per-layer metrics, read from its traced
smoke window: moe_tokens_per_expert and mfu.serve.reasoning from the
serve.step and serve.admit spans' tags, and decode_hbm_roofline from those
tags and the runs of jit_decode_fn, which the CPU's trace does not name
(stand-in runs of 1 ms a step are given it); all read None from a program
without the tags."""
import dataclasses
import math
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import chipbench_smoke as smoke  # noqa: E402
import harness  # noqa: E402
import peaks  # noqa: E402
import trace_reduce as tr  # noqa: E402

CELL = "serve.granite4h_small.reasoning"
KIND = "TPU v5 lite"


def reader(name):
    return harness.load_module(BENCH / "layer_metrics" / f"{name}.py",
                               f"layer_metric_{name}")


@pytest.fixture(scope="module")
def window():
    mp = pytest.MonkeyPatch()
    mp.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS[KIND]))
    try:
        yield smoke.run(CELL, trace=True)
    finally:
        mp.undo()


def test_traced_smoke_window_reads_the_hybrid_metrics(window):
    out, line = window
    assert line["correct"] is True
    # the tags are read without compiling inside the window
    assert line["setup"]["window_compiles"] == 0
    m = {k: v["value"] for k, v in line["metrics"].items()}
    for name in ("moe_tokens_per_expert.reasoning", "mfu.serve.reasoning"):
        assert math.isfinite(m[name]) and m[name] > 0, name
    steps = [s for s in out.ctx["program_spans"].named("serve.step")
             if "moe_routes_local" in s.tags]
    dims = out.ctx["stage_dims"]
    layers = dims["mamba_layers"] + dims["attn_layers"]
    assert m["moe_tokens_per_expert.reasoning"] == pytest.approx(
        sum(s.tags["moe_routes_local"] for s in steps)
        / (len(steps) * layers * dims["held"]))
    # the CPU's trace names no jit_decode_fn run: no time to divide by
    assert "decode_hbm_roofline.reasoning" not in m


def test_hbm_roofline_counts_the_steps_bytes(window):
    out, _ = window
    red = out.trace
    steps = [s for s in out.ctx["program_spans"].named("serve.step")
             if "moe_experts_hit" in s.tags]
    runs = [tr.Event("jit_decode_fn", red.t0 + 1e-3 * i,
                     red.t0 + 1e-3 * (i + 1)) for i in range(len(steps))]
    red = dataclasses.replace(red, modules=[red.modules[0] + runs])
    got = reader("decode_hbm_roofline").read(out.ctx, red)
    dims = out.ctx["stage_dims"]
    dense, expert = reader("decode_hbm_roofline").stage_matrix_bytes(dims)
    block = 2 * dims["block"] * dims["nkv"] * dims["hd"] * 2
    need = sum(dense + s.tags["moe_experts_hit"] * expert
               + s.tags["ssm_state_bytes"]
               + dims["attn_layers"] * s.tags["kv_blocks"] * block
               for s in steps)
    bw = peaks.peaks_for(KIND)["hbm_bytes_per_s"]
    assert math.isfinite(got) and got > 0
    assert got == pytest.approx(100 * need / bw / (1e-3 * len(steps)))


@pytest.mark.parametrize("name", ["moe_tokens_per_expert",
                                  "decode_hbm_roofline",
                                  "mfu.serve.reasoning"])
def test_hybrid_metrics_read_none_without_the_tags(window, name):
    """A program that tags no routings (the parent of this cell, or a
    dense model's steps) reads None."""
    out, _ = window
    ctx = dict(out.ctx)
    ps = ctx["program_spans"]
    saved = [dict(s.tags) for s in ps.spans]
    try:
        for s in ps.spans:
            for k in ("moe_routes_local", "moe_experts_hit",
                      "ssm_state_bytes"):
                s.tags.pop(k, None)
        assert reader(name).read(ctx, out.trace) is None
    finally:
        for s, tags in zip(ps.spans, saved):
            s.tags = tags
