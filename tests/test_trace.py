"""End-to-end operation tracing (repro.core.trace).

Load-bearing claims under test:

  * Determinism — the serialized span tree of a traced chaos run is a
    pure function of {seed, schedule}: byte-identical to_json() across
    repeats, and installing the tracer perturbs NOTHING (the SimNet
    delivery order and every Metrics counter match an untraced
    same-seed run exactly).
  * Cross-node propagation — one put's root span contains the leader's
    raft.append, every follower's follower.append (durable fsync
    included), and the apply spans on all three nodes; the tree stays
    connected across a leadership change, a node restart, and the
    InstallSnapshot fallback (learner catch-up).  Spans whose parent
    crossed a tracer swap are flagged ``orphan`` at export — kept,
    never silently dropped.
  * Causality auditor — zero violations on healthy and chaos runs;
    hand-built event streams with ack-before-durable,
    commit-before-quorum, apply-before-commit and
    client-ack-before-apply are each flagged.
  * Reconciliation — io-span byte sums equal the Metrics counter deltas
    for the same run, per (op, category).
  * MetricsRegistry — label validation, deterministic Prometheus text,
    JSON scrape; Cluster.registry() publishes per-node families.
  * SimNet drop attribution — dropped_msgs == sum(drop_reasons), with
    the partition/lossy/down/removed/crash_flush causes split out.
  * Device-path spans — with no tracer installed and no profiler session
    the checkpoint and serving paths open no span; under an installed
    tracer they give the documented tree; under a profiler session they
    land in a fresh profile tracer per session, each context span also a
    TraceAnnotation of the same name and duration in the profile.
"""
import glob
import json
import shutil
import tempfile
import time

import pytest

from repro.core import trace
from repro.core.cluster import Cluster
from repro.core.metrics import Metrics
from repro.core.raft import LEADER
from repro.core.simnet import SimNet
from repro.core.trace import MetricsRegistry, Tracer, audit, render_waterfall
from repro.core.workload import (ChaosSchedule, Tenant, WorkloadSpec,
                                 run_workload)

pytestmark = pytest.mark.trace


@pytest.fixture(autouse=True)
def _no_global_tracer_leaks():
    """The tracer is process-global (faultfs pattern): never let one
    test's tracer observe another test's cluster."""
    trace.uninstall()
    yield
    trace.uninstall()


def _mk(seed=4, sync=False, **engine_kw):
    wd = tempfile.mkdtemp(prefix="trace_")
    kw = {"gc_threshold": 1 << 60}
    kw.update(engine_kw)
    return Cluster(n=3, engine="nezha", workdir=wd, seed=seed,
                   sync=sync, engine_kwargs=kw)


def _close(c):
    for e in c.engines:
        if e is not None:
            e.close()


# ------------------------------------------------------------ determinism
def _traced_chaos_json(chaos_seed=11, cluster_seed=4, n_ops=120):
    c = _mk(seed=cluster_seed)
    t = c.enable_tracing()
    spec = WorkloadSpec(rate=5000.0, n_ops=n_ops, n_keys=60, vsize=64,
                        seed=3, tenants=(Tenant("t", 1.0, "A"),))
    rep = run_workload(c, spec, ChaosSchedule.generate(chaos_seed,
                                                       n_cycles=2))
    c.disable_tracing()
    out = t.to_json()
    _close(c)
    return out, rep


def test_same_seed_byte_identical_trace():
    j1, rep1 = _traced_chaos_json()
    j2, rep2 = _traced_chaos_json()
    assert j1 == j2, "span tree diverged across same-{seed, schedule} runs"
    assert rep1.violations == []
    doc = json.loads(j1)
    assert doc["spans"] and doc["events"] and doc["net_events"]
    # chaos faults are annotated into the event stream, time-aligned
    assert any(e["kind"] == "fault" for e in doc["events"])


def test_tracer_does_not_perturb_the_simulation():
    """Same seed, tracer on vs off: identical SimNet delivery order and
    identical byte accounting — tracing is pure observation."""
    runs = []
    for traced in (False, True):
        c = _mk(seed=6)
        c.net.enable_trace()
        if traced:
            c.enable_tracing()
        c.elect()
        for i in range(25):
            c.put(b"k%04d" % i, b"v" * 64)
        assert c.get(b"k0007") == b"v" * 64
        runs.append((list(c.net.trace), c.net.time, c.net.sent_msgs,
                     [dict(m.write_bytes) for m in c.metrics],
                     [m.fsyncs for m in c.metrics]))
        c.disable_tracing()
        _close(c)
    assert runs[0] == runs[1]


# ------------------------------------------------------- span propagation
def test_put_root_span_connects_all_three_nodes():
    c = _mk(sync=True)
    t = c.enable_tracing()
    c.elect()
    idx = c.put(b"alpha", b"beta" * 16)
    for _ in range(100):        # drain the followers' apply pipelines
        if all(nd.last_applied >= idx for nd in c.nodes if nd is not None):
            break
        c.tick()
    (root,) = t.roots("put")
    assert root.tags["index"] == idx
    sub = t.subtree(root.sid)
    ld = c.leader()
    touched = {s.node for s in sub if s.kind == "raft"}
    assert touched == {0, 1, 2}, "follower appends not grafted onto root"
    applies = {s.node for s in sub if s.name == "apply"}
    assert applies == {0, 1, 2}
    # the leader's durable point: exactly one value-log fsync on the
    # put's critical path (the Raft-log-IS-the-ValueLog design)
    leader_vlog_fsyncs = [s for s in sub if s.name == "io.fsync"
                          and s.node == ld.nid
                          and s.tags["category"] == "valuelog"]
    assert len(leader_vlog_fsyncs) == 1
    assert audit(t.events) == []
    # the waterfall renders the same tree for humans
    art = render_waterfall(t, root.sid)
    assert "put" in art and "follower.append" in art
    _close(c)


def test_propagation_across_leadership_change():
    c = _mk(sync=True)
    t = c.enable_tracing()
    ld = c.elect()
    for i in range(8):
        c.put(b"k%04d" % i, b"v" * 32)
    c.crash(ld.nid)
    new = c.elect()
    assert new.nid != ld.nid
    for i in range(8, 16):
        c.put(b"k%04d" % i, b"v" * 32)
    assert c.get(b"k0012") == b"v" * 32
    assert audit(t.events) == [], "failover broke a causality invariant"
    roots = t.roots("put")
    assert len(roots) == 16
    # post-failover puts graft onto the NEW leader and stay connected
    late = t.subtree(roots[-1].sid)
    assert any(s.name == "follower.append" for s in late)
    assert not any(d.get("orphan") for d in t.export()["spans"])
    _close(c)


def test_propagation_across_node_restart():
    c = _mk(sync=True)
    t = c.enable_tracing()
    ld = c.elect()
    victim = (ld.nid + 1) % 3
    for i in range(6):
        c.put(b"k%04d" % i, b"v" * 32)
    c.crash(victim)
    for i in range(6, 12):
        c.put(b"k%04d" % i, b"v" * 32)
    c.restart(victim)
    for _ in range(400):
        nd = c.nodes[victim]
        if nd is not None and nd.last_applied >= c.leader().commit_index:
            break
        c.tick()
    # the restarted node re-acked its recovered log: the baseline events
    # emitted at recovery keep that from reading as ack-before-durable
    assert any(e["kind"] == "durable" and e.get("baseline")
               and e["node"] == victim for e in t.events)
    assert audit(t.events) == []
    assert not any(d.get("orphan") for d in t.export()["spans"])
    _close(c)


def test_propagation_across_install_snapshot_fallback():
    """Learner catch-up goes through InstallSnapshot: the install span
    lands on the new node, the snapshot counts as durable+applied for
    the auditor, and the tree stays connected."""
    c = _mk(sync=True, gc_threshold=4096)
    t = c.enable_tracing()
    c.elect()
    for i in range(30):
        c.put(b"k%04d" % i, b"v%04d" % i)
    c.force_gc()
    new = c.add_node()
    assert c.wait_promoted(new)
    installs = [s for s in t.spans if s.name == "install_snapshot"]
    assert installs and any(s.node == new for s in installs)
    assert any(e["kind"] == "snapshot_install" and e["node"] == new
               for e in t.events)
    assert audit(t.events) == []
    assert not any(d.get("orphan") for d in t.export()["spans"])
    _close(c)


def test_orphan_spans_flagged_not_dropped():
    t = Tracer()
    sid = t.begin("stray", parent=9999)
    t.end(sid)
    (d,) = t.export()["spans"]
    assert d["orphan"] is True and d["name"] == "stray"
    # a span whose parent EXISTS is not flagged
    t2 = Tracer()
    root = t2.begin("root")
    kid = t2.begin("kid")
    t2.end(kid)
    t2.end(root)
    assert not any(s.get("orphan") for s in t2.export()["spans"])


def test_mid_run_tracer_install_emits_baselines():
    """Installing the tracer on a cluster with history must seed
    durable/commit/apply baselines, or the first post-install ack reads
    as a violation."""
    c = _mk(sync=True)
    c.elect()
    for i in range(10):
        c.put(b"k%04d" % i, b"v" * 32)
    t = c.enable_tracing()           # mid-run: state predates the tracer
    for i in range(10, 20):
        c.put(b"k%04d" % i, b"v" * 32)
    assert audit(t.events) == []
    kinds = {e["kind"] for e in t.events if e.get("baseline")}
    assert {"durable", "commit_learned", "apply"} <= kinds
    _close(c)


# -------------------------------------------------------------- auditor
def test_audit_flags_each_violation_class():
    base = {"t": 0}

    def ev(kind, node, index, **kw):
        return dict(base, kind=kind, node=node, index=index, **kw)

    # ack before durable
    v = audit([ev("ack_sent", 1, 5, to=0)])
    assert len(v) == 1 and "before durable" in v[0]
    # commit without quorum: only the leader's own durability
    v = audit([ev("durable", 0, 5),
               ev("commit", 0, 5, voters=[0, 1, 2])])
    assert len(v) == 1 and "before quorum" in v[0]
    # apply before commit
    v = audit([ev("durable", 2, 5), ev("apply", 2, 5)])
    assert len(v) == 1 and "before commit" in v[0]
    # client ack before apply
    v = audit([ev("client_ack", 0, 5)])
    assert len(v) == 1 and "before apply" in v[0]


def test_audit_accepts_clean_protocol_round():
    evs = [
        {"t": 0, "kind": "durable", "node": 0, "index": 1},
        {"t": 1, "kind": "durable", "node": 1, "index": 1},
        {"t": 1, "kind": "ack_sent", "node": 1, "index": 1, "to": 0},
        {"t": 2, "kind": "ack_recv", "node": 0, "index": 1, "from": 1},
        {"t": 2, "kind": "commit", "node": 0, "index": 1,
         "voters": [0, 1, 2]},
        {"t": 3, "kind": "apply", "node": 0, "index": 1},
        {"t": 3, "kind": "client_ack", "node": 0, "index": 1},
        {"t": 4, "kind": "fault", "node": -1, "index": 0},  # annotation
    ]
    assert audit(evs) == []
    # snapshot_install stands in for durable+commit+apply
    assert audit([
        {"t": 0, "kind": "snapshot_install", "node": 3, "index": 9},
        {"t": 1, "kind": "ack_sent", "node": 3, "index": 9, "to": 0},
        {"t": 2, "kind": "apply", "node": 3, "index": 9},
    ]) == []


# -------------------------------------------------------- reconciliation
def test_io_span_sums_reconcile_with_metrics_counters():
    """Every byte the Metrics counters saw during the traced window is
    an io span, and vice versa — exact, not approximate."""
    c = _mk(sync=True)
    c.elect()
    before = [m.snapshot() for m in c.metrics]
    t = c.enable_tracing()
    for i in range(20):
        c.put(b"r%04d" % i, b"x" * 96)
    assert c.get(b"r0011") == b"x" * 96
    c.disable_tracing()
    sums = t.io_sums()
    for op, attr in (("write", "write_bytes"), ("read", "read_bytes")):
        want = {}
        for m, b4 in zip(c.metrics, before):
            for cat, n in m.delta(b4)[attr].items():
                want[cat] = want.get(cat, 0) + n
        got = {cat: n for (o, cat), n in sums.items() if o == op}
        got = {k: v for k, v in got.items() if v}
        want = {k: v for k, v in want.items() if v}
        assert got == want, f"{op} bytes diverged from Metrics"
    fsyncs = sum(1 for s in t.spans if s.name == "io.fsync")
    want_fsyncs = sum(m.delta(b4)["fsyncs"]
                      for m, b4 in zip(c.metrics, before))
    assert fsyncs == want_fsyncs
    _close(c)


# ------------------------------------------------------ metrics registry
def test_registry_families_and_exposition():
    reg = MetricsRegistry()
    ops = reg.counter("repro_ops_total", "ops by kind", ["kind"])
    ops.labels(kind="put").inc(3)
    ops.labels(kind="get").inc()
    reg.gauge("repro_up", "liveness").set(1)
    h = reg.histogram("repro_lat_us", "latency", ["op"])
    for v in (10, 20, 30):
        h.labels(op="put").observe(v)
    text = reg.prometheus_text()
    assert '# TYPE repro_ops_total counter' in text
    assert 'repro_ops_total{kind="put"} 3' in text
    assert 'repro_lat_us_count{op="put"} 3' in text
    assert '# TYPE repro_lat_us summary' in text
    assert text == reg.prometheus_text()        # deterministic
    doc = reg.scrape()
    assert doc["repro_ops_total"]["samples"][0]["labels"] == {"kind": "get"}
    json.dumps(doc)                             # scrape is JSON-able
    with pytest.raises(ValueError, match="takes labels"):
        ops.labels(wrong="x")
    with pytest.raises(ValueError, match="re-registered"):
        reg.gauge("repro_ops_total", "", ["kind"])


def test_cluster_registry_and_health_report_publish_metrics():
    c = _mk(sync=True)
    c.elect()
    for i in range(5):
        c.put(b"m%04d" % i, b"v" * 64)
    text = c.prometheus_text()
    assert 'repro_fsyncs_total{category="valuelog",node="0"}' in text
    assert 'repro_node_up{node="1"} 1' in text
    hr = c.health_report()
    json.dumps(hr)
    assert hr["metrics"]["repro_raft_commit_index"]["samples"]
    assert hr["net"]["drop_reasons"] == {}
    # per-node fsync categories also surface via Metrics.summary()
    assert c.metrics[0].summary()["fsync_cats"].get("valuelog", 0) > 0
    _close(c)


# ------------------------------------------------- simnet drop attribution
def test_drop_reasons_partition_lossy_down_removed():
    net = SimNet([0, 1, 2], seed=1)
    net.partition(0, 1)
    net.send(0, 1, "m")
    net.heal()
    net.crash(2)
    net.send(0, 2, "m")
    net.restart(2)
    net.drop_prob = 1.0
    net.send(0, 1, "m")
    net.drop_prob = 0.0
    net.send(0, 2, "in-flight")      # queued, then destroyed by crash
    net.crash(2)
    net.restart(2)
    net.remove_node(1)
    net.send(0, 1, "m")
    assert dict(net.drop_reasons) == {
        "partition": 1, "down": 1, "lossy": 1, "crash_flush": 1,
        "removed": 1}
    assert net.dropped_msgs == sum(net.drop_reasons.values())


def test_drops_flow_into_tracer_net_events():
    t = trace.install(Tracer())
    try:
        net = SimNet([0, 1], seed=1)
        net.partition(0, 1)
        net.send(0, 1, "x")
        net.heal()
        net.send(0, 1, "y")
        net.time = 100
        net.deliver(1)
        kinds = [(e[0], e[5]) for e in t.net_events]
        assert ("drop", "partition") in kinds
        assert ("send", None) in kinds and ("deliver", None) in kinds
    finally:
        trace.uninstall()


# ------------------------------------------------------ device-path spans
@pytest.fixture(scope="module")
def device_paths():
    """A smoke TrainRunner (no control plane) and a smoke serving engine,
    compiled once for the tests below; `drive()` runs two saves (the store
    set to GC after each), a restore, and four requests through two slots
    with a compaction."""
    import jax

    from repro.configs import ShapeConfig, get
    from repro.launch.mesh import make_host_mesh
    from repro.runtime.coordinator import TrainRunner
    from repro.serve.engine import ServingEngine

    cfg = get("smollm_135m", smoke=True)
    wd = tempfile.mkdtemp(prefix="trace_ckpt_")
    runner = TrainRunner(cfg, ShapeConfig("t", seq_len=32, global_batch=4,
                                          kind="train"),
                         make_host_mesh(), wd, seed=1, ckpt_every=2)
    runner.store.gc_threshold = 0
    runner.init_or_restore()
    eng = ServingEngine(cfg.replace(param_dtype="float32", kv_block_size=8),
                        max_slots=2, max_seq=32, seed=0)

    def drive():
        runner.run(runner.start_step + 4)
        runner.init_or_restore()
        reqs = [eng.submit([3 + i, 1, 4], max_new=3) for i in range(4)]
        eng.run_until_drained()
        eng.compact(backend="reference")
        jax.block_until_ready(eng.caches)
        return reqs

    drive()                                         # compiles
    yield drive
    runner.store.close()
    shutil.rmtree(wd, ignore_errors=True)


def test_device_paths_record_no_span_when_tracing_is_off(device_paths,
                                                         monkeypatch):
    opened = []
    for meth in ("begin", "record"):
        orig = getattr(Tracer, meth)
        monkeypatch.setattr(Tracer, meth, lambda self, *a, _o=orig, **k:
                            opened.append(a[0]) or _o(self, *a, **k))
    before = trace.profiled()
    device_paths()
    assert opened == []
    assert trace.profiled() is before


def test_device_paths_give_the_span_tree_under_an_installed_tracer(
        device_paths):
    t = trace.install(Tracer(clock=time.perf_counter))
    reqs = device_paths()
    trace.uninstall()
    by_id = {s.sid: s for s in t.spans}

    def parents(name):
        return [by_id[s.parent].name if s.parent else None
                for s in t.spans if s.name == name]

    assert len(parents("ckpt.save")) == 2
    assert parents("ckpt.save.fetch") == ["ckpt.save"] * 2
    assert parents("ckpt.store.append") == ["ckpt.save"] * 2
    assert parents("ckpt.store.gc") == ["ckpt.save"] * 2
    assert "ckpt.store.gc" in parents("ckpt.store.manifest")
    assert parents("ckpt.restore.place") == ["ckpt.restore"]
    admits = [s for s in t.spans if s.name == "serve.admit"]
    assert parents("serve.admit") == ["serve.step"] * len(reqs)
    assert [(s.tags["prompt_tokens"], s.tags["padded_tokens"])
            for s in admits] == [(3, 32)] * len(reqs)
    queued = [s for s in t.spans if s.name == "serve.queued"]
    assert len(queued) == len(reqs)
    assert sorted(s.t1 - s.t0 for s in queued) == sorted(
        r.admitted - r.submitted for r in reqs)
    assert all(r.submitted <= r.admitted <= r.finished for r in reqs)
    assert parents("serve.compact") == [None]
    assert all(s.t1 is not None and s.t1 >= s.t0 for s in t.spans)


def _annotations(log_dir):
    import jax
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.duration_ns * 1e-9))
    return {k: [d for _, d in sorted(v)] for k, v in out.items()}


def test_profile_spans_are_trace_annotations_of_the_same_duration(
        device_paths, tmp_path):
    import jax
    with jax.profiler.trace(str(tmp_path)):
        device_paths()
    t = trace.profiled()
    assert t is not None and t.dropped == 0
    spans = [s for s in t.spans if s.kind == "span"]
    assert {s.name for s in spans} >= {
        "ckpt.save", "ckpt.save.fetch", "ckpt.store.append",
        "ckpt.store.manifest", "ckpt.store.gc", "ckpt.restore",
        "ckpt.restore.place", "serve.step", "serve.admit", "serve.compact"}
    assert [s.name for s in t.spans if s.kind == "record"] == \
        ["serve.queued"] * 4
    annotated = _annotations(tmp_path)
    for name in {s.name for s in spans}:
        mine = [s.t1 - s.t0 for s in sorted(spans, key=lambda s: s.t0)
                if s.name == name]
        assert len(annotated[name]) == len(mine), name
        for a, b in zip(annotated[name], mine):
            assert abs(a - b) < 50e-6, (name, a, b)


def test_each_profiler_session_starts_a_fresh_profile_tracer(tmp_path):
    import jax
    with jax.profiler.trace(str(tmp_path / "one")):
        with trace.span("a"):
            pass
    first = trace.profiled()
    with trace.span("off"):                 # no session: a no-op
        pass
    assert trace.profiled() is first
    assert [s.name for s in first.spans] == ["a"]
    with jax.profiler.trace(str(tmp_path / "two")):
        with trace.span("b"):
            trace.record("c", 1.0, 2.0)
    second = trace.profiled()
    assert second is not first
    assert [s.name for s in second.spans] == ["b", "c"]
    assert second.spans[1].parent == second.spans[0].sid
    assert [s.name for s in first.spans] == ["a"]


def test_tracer_keeps_at_most_max_spans_and_counts_the_rest():
    t = Tracer(clock=time.perf_counter, max_spans=3)
    for i in range(4):
        with t.span("s%d" % i):
            t.record("r", 0.0, 1.0)
    assert [s.name for s in t.spans] == ["s0", "r", "s1"]
    assert t.dropped == 5
    assert t._stack == []
