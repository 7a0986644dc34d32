"""`pytest -m smoke` target wrapping benchmarks/run.py --smoke: every engine
sustains puts through the batched pipeline, nezha beats original on value
write bytes, and group commit cuts fsyncs.  The gate's artifact goes to a
temporary directory, not the checkout (`make smoke` writes it there)."""
import pytest


@pytest.mark.smoke
def test_smoke_benchmark_gate(tmp_path, monkeypatch):
    from benchmarks import common
    from benchmarks.run import smoke
    monkeypatch.setattr(common, "ARTIFACT_DIR", str(tmp_path))
    assert smoke() == 0
    assert (tmp_path / "BENCH_smoke.json").exists()
