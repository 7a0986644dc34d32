"""Persistence waterfall — where does one put's durability actually go?

Traced runs (repro.core.trace) answer the paper's mechanism question
structurally instead of statistically: each client put opens a root
span, the context rides every AppendEntries, and the leader's + both
followers' fsyncs land INSIDE that put's subtree with their layer tag.
The figure reports, per engine:

  * the put critical path — fsyncs and value bytes on the LEADER under
    each put's root span, split by layer (nezha: ONE valuelog fsync,
    the Raft-log-IS-the-ValueLog design; original: the value pays both
    the raft_log append and the WAL),
  * the cluster-wide persistence bill for the same put (all nodes),
  * per-tier read paths (linearizable / lease / session) — bytes and
    read ops under each get's root span,
  * GC interference — how much gc.flush/gc.merge span time lands inside
    the put window once the store cycles,
  * reconciliation — io-span byte sums equal the Metrics counter deltas
    for the same window, category for category (asserted, not eyeballed).

smoke_gate() is CI gate #9: a traced chaos run (leader kill + lossy
window) audits to ZERO causality violations; every synced nezha put
carries exactly one value-log fsync on the leader critical path; and a
tracer left uninstalled costs nothing — the same-seed untraced run has
the identical SimNet trace, identical Metrics, and comparable wall time.
"""
from __future__ import annotations

import contextlib
import tempfile
import time

from benchmarks import common
from repro.core import trace
from repro.core.client import LEASE, LINEARIZABLE, SESSION
from repro.core.cluster import Cluster
from repro.core.workload import (ChaosSchedule, FaultEvent, Tenant,
                                 WorkloadSpec, run_workload)

N_PUTS = 48 if common.FULL else 16
VSIZE = 1024


def _sync_cluster(engine: str, seed: int = 7, **engine_kw) -> Cluster:
    wd = tempfile.mkdtemp(prefix=f"bench_trace_{engine}_")
    kw = {}
    if engine == "nezha":
        kw = {"gc_threshold": 1 << 60, "gc_batch": 128}
    kw.update(engine_kw)
    c = Cluster(n=3, engine=engine, workdir=wd, seed=seed, sync=True,
                engine_kwargs=kw)
    for eng in c.engines:
        if hasattr(eng, "db"):
            eng.db.memtable_limit = 256 << 10
            eng.db.l0_limit = 2
    c.elect()
    return c


def _crit(t: trace.Tracer, root, leader: int):
    """Leader-side persistence under one root span: (fsyncs-by-category,
    write-bytes-by-category)."""
    fsyncs: dict = {}
    wbytes: dict = {}
    for s in t.subtree(root.sid):
        if s.kind != "io" or s.node != leader:
            continue
        cat = s.tags.get("category", "?")
        if s.name == "io.fsync":
            fsyncs[cat] = fsyncs.get(cat, 0) + 1
        elif s.name == "io.write":
            wbytes[cat] = wbytes.get(cat, 0) + int(s.tags.get("bytes", 0))
    return fsyncs, wbytes


def _fmt_cats(d: dict) -> str:
    return ",".join(f"{k}:{v}" for k, v in sorted(d.items())) or "none"


def _reconcile(t: trace.Tracer, c: Cluster, before) -> bool:
    """Exact cross-check: io-span sums == Metrics deltas, per category."""
    sums = t.io_sums()
    for op, attr in (("write", "write_bytes"), ("read", "read_bytes")):
        want: dict = {}
        for m, b4 in zip(c.metrics, before):
            for cat, n in m.delta(b4)[attr].items():
                want[cat] = want.get(cat, 0) + n
        got = {cat: n for (o, cat), n in sums.items() if o == op and n}
        if got != {k: v for k, v in want.items() if v}:
            return False
    nspans = sum(1 for s in t.spans if s.name == "io.fsync")
    return nspans == sum(m.delta(b4)["fsyncs"]
                         for m, b4 in zip(c.metrics, before))


def waterfall(engine: str) -> tuple:
    """One row: the avg put critical path + cluster bill, reconciled."""
    c = _sync_cluster(engine)
    before = [m.snapshot() for m in c.metrics]
    t = c.enable_tracing()
    items = common.keys_values(N_PUTS, VSIZE)
    t0 = time.perf_counter()
    for k, v in items:
        c.put(k, v)
    dt = time.perf_counter() - t0
    ld = c.leader()
    c.disable_tracing()
    roots = t.roots("put")
    crit_f: dict = {}
    crit_b: dict = {}
    cluster_f = 0
    for root in roots:
        f, b = _crit(t, root, ld.nid)
        for k2, v2 in f.items():
            crit_f[k2] = crit_f.get(k2, 0) + v2
        for k2, v2 in b.items():
            crit_b[k2] = crit_b.get(k2, 0) + v2
        cluster_f += sum(1 for s in t.subtree(root.sid)
                         if s.name == "io.fsync")
    n = max(len(roots), 1)
    rec = _reconcile(t, c, before)
    row = (f"fig_trace_waterfall/{engine}", 1e6 * dt / n,
           f"puts={len(roots)}"
           f";crit_fsyncs_per_put={sum(crit_f.values()) / n:.2f}"
           f";crit_fsync_cats={_fmt_cats(crit_f)}"
           f";crit_write_bytes_per_put={sum(crit_b.values()) / n:.0f}"
           f";crit_write_cats={_fmt_cats(crit_b)}"
           f";cluster_fsyncs_per_put={cluster_f / n:.2f}"
           f";reconciled={int(rec)}"
           f";violations={len(trace.audit(t.events))}")
    common.destroy(c)
    return row


def read_paths() -> list:
    """Per-tier read rows: bytes + read ops under each get's root span."""
    c = _sync_cluster("nezha")
    items = common.keys_values(N_PUTS, VSIZE)
    for k, v in items:
        c.put(k, v)
    t = c.enable_tracing()
    rows = []
    sess = c.session()
    for tier, kw in ((LINEARIZABLE, {}), (LEASE, {}),
                     (SESSION, {"session": sess})):
        mark = len(t.spans)
        for k, v in items[: N_PUTS // 2]:
            assert c.get(k, tier, **kw) == v
        gets = [s for s in t.spans[mark:]
                if s.parent == 0 and s.name == "get"]
        rbytes = rops = 0
        for g in gets:
            for s in t.subtree(g.sid):
                if s.name == "io.read":
                    rbytes += int(s.tags.get("bytes", 0))
                    rops += 1
        n = max(len(gets), 1)
        rows.append((f"fig_trace_reads/{tier}", 0.0,
                     f"gets={len(gets)};read_bytes_per_get={rbytes / n:.0f}"
                     f";read_ops_per_get={rops / n:.2f}"))
    c.disable_tracing()
    common.destroy(c)
    return rows


def gc_interference() -> tuple:
    """Low-threshold cluster: how much GC span time lands inside the put
    window, and does the audit stay clean while GC interleaves."""
    c = _sync_cluster("nezha", gc_threshold=24 << 10, gc_batch=64)
    t = c.enable_tracing()
    items = common.keys_values(3 * N_PUTS, 1024)
    for k, v in items:
        c.put(k, v)
    c.disable_tracing()
    gc_spans = [s for s in t.spans if s.kind == "gc"]
    gc_ticks = sum((s.t1 or s.t0) - s.t0 for s in gc_spans)
    put_ticks = sum((s.t1 or s.t0) - s.t0 for s in t.roots("put"))
    viol = trace.audit(t.events)
    row = ("fig_trace_gc_interference/nezha", 0.0,
           f"gc_spans={len(gc_spans)};gc_ticks={gc_ticks}"
           f";put_ticks={put_ticks}"
           f";gc_share={gc_ticks / max(gc_ticks + put_ticks, 1):.3f}"
           f";violations={len(viol)}")
    common.destroy(c)
    return row


def smoke_gate() -> list:
    """CI gate #9 (see benchmarks/run.py smoke())."""
    rows = []
    # (a) traced chaos: leader kill + lossy window, zero violations
    wd = tempfile.mkdtemp(prefix="trace_gate_chaos_")
    c = Cluster(n=3, engine="nezha", workdir=wd, seed=17,
                engine_kwargs={"gc_threshold": 1 << 60})
    t = c.enable_tracing()
    spec = WorkloadSpec(rate=5000.0, n_ops=160, n_keys=64, vsize=256,
                        seed=5, tenants=(Tenant("t", 1.0, "A"),))
    sched = ChaosSchedule([FaultEvent(0.20, "kill_leader"),
                           FaultEvent(0.45, "restart", recovery=True),
                           FaultEvent(0.60, "lossy", 0.15),
                           FaultEvent(0.80, "heal_lossy", recovery=True)],
                          seed=17)
    rep = run_workload(c, spec, sched)
    c.disable_tracing()
    viol = trace.audit(t.events)
    faults = [e["kind"] for e in t.events if e["kind"] == "fault"]
    lossy_drops = c.net.drop_reasons.get("lossy", 0)
    rows.append(("smoke_trace/chaos_audit", 0.0,
                 f"causality_violations={len(viol)}"
                 f";history_violations={len(rep.violations)}"
                 f";faults_annotated={len(faults)}"
                 f";lossy_drops={lossy_drops}"
                 f";spans={len(t.spans)}"))
    common.destroy(c)

    # (b) put critical path: EXACTLY one value-log fsync per commit
    # window on the leader, for every synced nezha put
    c = _sync_cluster("nezha", seed=9)
    t = c.enable_tracing()
    for k, v in common.keys_values(12, 512, seed=2):
        c.put(k, v)
    ld = c.leader()
    c.disable_tracing()
    per_put = [_crit(t, r, ld.nid)[0].get("valuelog", 0)
               for r in t.roots("put")]
    rows.append(("smoke_trace/put_critical_path", 0.0,
                 f"puts={len(per_put)}"
                 f";vlog_fsyncs_min={min(per_put)}"
                 f";vlog_fsyncs_max={max(per_put)}"))
    common.destroy(c)

    # (c) disabled-tracer footprint: the untraced same-seed run records
    # nothing into the trace module and is identical in simulation terms
    # to the traced one; the wall-clock ratio is reported, not gated
    sig = []
    walls = []
    records = []
    for traced in (False, True):
        c2 = _sync_cluster("nezha", seed=13)
        c2.net.enable_trace()
        if traced:
            c2.enable_tracing()
        with _count_records() as n:
            w0 = time.perf_counter()
            for k, v in common.keys_values(24, 512, seed=3):
                c2.put(k, v)
            walls.append(time.perf_counter() - w0)
        records.append(n[0])
        sig.append((list(c2.net.trace), c2.net.time,
                    [dict(m.write_bytes) for m in c2.metrics],
                    [m.fsyncs for m in c2.metrics]))
        c2.disable_tracing()
        common.destroy(c2)
    ratio = walls[1] / max(walls[0], 1e-9)
    rows.append(("smoke_trace/disabled_footprint", 0.0,
                 f"sim_identical={int(sig[0] == sig[1])}"
                 f";disabled_records={records[0]}"
                 f";traced_records={records[1]}"
                 f";wall_ratio={ratio:.2f}"))
    return rows


# the trace module's recording calls: a Tracer's spans, I/O ops, audit and
# network events and tags, and a MetricsRegistry's counter, gauge and
# histogram updates
_RECORDING = ((trace.Tracer, ("begin", "record", "io", "event",
                              "net_event", "tag", "register_index")),
              (trace._Child, ("inc", "set")),
              (trace._HistChild, ("observe",)))


@contextlib.contextmanager
def _count_records():
    """Counts the trace module's recording calls made while the block
    runs, by any tracer or registry; yields a one-element list."""
    n = [0]
    saved = [(cls, m, getattr(cls, m)) for cls, ms in _RECORDING
             for m in ms]

    def counted(f):
        def call(*a, **k):
            n[0] += 1
            return f(*a, **k)
        return call

    for cls, m, f in saved:
        setattr(cls, m, counted(f))
    try:
        yield n
    finally:
        for cls, m, f in saved:
            setattr(cls, m, f)


def run():
    rows = [waterfall("nezha"), waterfall("original")]
    rows += read_paths()
    rows.append(gc_interference())
    rows += smoke_gate()
    return rows


if __name__ == "__main__":
    rows = run()
    common.emit(rows)
    path = common.write_artifact("fig_trace", rows)
    import sys
    print(f"# wrote {path}", file=sys.stderr)
    # one annotated waterfall for humans (also: examples/trace_put.py)
    c = _sync_cluster("nezha")
    t = c.enable_tracing()
    c.put(b"demo-key", b"demo-value" * 32)
    c.disable_tracing()
    (root,) = t.roots("put")
    print(trace.render_waterfall(t, root.sid), file=sys.stderr)
    common.destroy(c)
