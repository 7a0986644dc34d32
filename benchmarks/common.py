"""Shared benchmark machinery for the paper-figure reproductions.

Scaled-down sizes (CPU container; the paper used 100GB/3-node SSD clusters):
quick mode loads a few MB per engine.  Every figure reports BOTH wall-clock
throughput/latency and the byte-accounted write/read traffic — the byte
ratios are size-invariant and carry the paper's mechanism claims.

Set REPRO_BENCH_FULL=1 for ~10x larger runs.
"""
from __future__ import annotations

import os
import shutil
import tempfile
import time
from typing import Dict, List, Tuple

import numpy as np

from repro.core.cluster import Cluster

FULL = os.environ.get("REPRO_BENCH_FULL") == "1"
ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..")
ENGINES = ["original", "pasv", "tikv", "dwisckey", "lsmraft", "nezha_nogc",
           "nezha"]

# byte categories in which the VALUE itself hits disk (excludes 8B offsets);
# single source of truth for fig4 and the smoke gate
VALUE_CATS = {"raft_log", "wal", "flush", "compaction", "valuelog",
              "wisckey_vlog", "sst_ship"}


def make_cluster(engine: str, n: int = 3, seed: int = 7,
                 gc_threshold: int = 2 << 20) -> Cluster:
    wd = tempfile.mkdtemp(prefix=f"bench_{engine}_")
    kw = {}
    if engine == "nezha":
        kw = {"gc_threshold": gc_threshold, "gc_batch": 128}
    c = Cluster(n=n, engine=engine, workdir=wd, seed=seed, engine_kwargs=kw)
    # make Original-family engines actually flush/compact at bench scale
    for eng in c.engines:
        if hasattr(eng, "db"):
            eng.db.memtable_limit = 256 << 10
            eng.db.l0_limit = 2
    c.elect()
    return c


def keys_values(n: int, vsize: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        key = f"user{i:010d}".encode()
        val = rng.integers(0, 256, vsize, dtype=np.uint8).tobytes()
        out.append((key, val))
    return out


def zipf_indices(n_ops: int, n_keys: int, seed: int = 1, a: float = 1.2):
    rng = np.random.default_rng(seed)
    idx = rng.zipf(a, size=n_ops * 2)
    idx = idx[idx <= n_keys][:n_ops] - 1
    while len(idx) < n_ops:
        more = rng.zipf(a, size=n_ops)
        more = more[more <= n_keys] - 1
        idx = np.concatenate([idx, more])[:n_ops]
    return idx.astype(int)


def timed(fn, *args, **kw) -> Tuple[float, object]:
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return time.perf_counter() - t0, out


def leader_metrics(c: Cluster):
    ld = c.elect()
    return c.metrics[ld.nid], c.engines[ld.nid]


def emit(rows: List[Tuple[str, float, str]]):
    """CSV contract from the harness skeleton: name,us_per_call,derived."""
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")


def parse_derived(derived: str) -> Dict[str, object]:
    """'ops_s=997;gc_cycles=3' -> {'ops_s': 997.0, 'gc_cycles': 3.0}; non-
    numeric fields pass through as strings."""
    out: Dict[str, object] = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            out[k] = float(v)
        except ValueError:
            out[k] = v
    return out


def write_artifact(fig: str, rows: List[Tuple[str, float, str]],
                   extra: Dict[str, object] = None) -> str:
    """Persist one figure's results as BENCH_<fig>.json in ARTIFACT_DIR
    (the repo root) so the perf trajectory is tracked (and diffed) across
    PRs."""
    import json
    path = os.path.join(ARTIFACT_DIR, f"BENCH_{fig}.json")
    doc = {"fig": fig, "full": FULL,
           "rows": [{"name": n, "us_per_call": round(us, 2),
                     "derived": parse_derived(d)} for n, us, d in rows]}
    if extra:
        doc.update(extra)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    return os.path.abspath(path)


def destroy(c: Cluster):
    c.destroy()
