"""Benchmark harness — one module per paper table/figure + the roofline
table from the dry-run.  Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run            # quick mode
  REPRO_BENCH_FULL=1 ... python -m benchmarks.run    # ~10x sizes
  python -m benchmarks.run --only fig4,roofline   # roofline: on request only
  python -m benchmarks.run --smoke                   # tiny CI gate (tier-1)
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def smoke() -> int:
    """Tiny all-engine gate runnable in the tier-1 time budget.

    Asserts the four load-bearing claims survive the pipeline:
      1. nezha writes no more value bytes per user byte than original
         (the paper's >=3x -> 1x story),
      2. group commit actually cuts fsyncs: batch=32 uses < 1/4 the fsyncs
         of batch=1 on a small synced nezha run,
      3. leveled GC (fig10 at smoke scale) keeps per-cycle flush work flat
         while sustaining puts through multiple GC cycles,
      4. run shipping (fig_runship at smoke scale) keeps follower GC flush
         bytes at ~0 and cuts cluster-wide GC rewrite work vs the local-GC
         baseline, with leader/follower scans byte-identical,
      5. the consistency-tiered read API (fig_reads at smoke scale):
         SESSION reads served by followers return byte-equal scans vs the
         leader, and LEASE reads perform ZERO heartbeat-quorum rounds
         under a stable leader,
      6. chaos gate (fig_tail at smoke scale): an open-loop YCSB-A run
         through one seeded leader kill-and-recover cycle yields ZERO
         linearizability/session violations, both faults fire, and the
         recovered-phase p99 stays within 10x of the steady-state p99.
         Latency runs on the virtual clock (SimNet ticks), so the whole
         row is seed-deterministic and needs exactly one attempt,
      7. durability gate (crash-point sweep): a seeded 64-point kill -9
         sweep over the probe workload's numbered I/O ops — picks spread
         across the op range, cycling drop/torn/lost_rename — recovers
         every time with zero acked-write loss and a clean structural
         audit, and one full-cluster restart at a torn point converges
         byte-equal.  Any failure reproduces from {seed, crash_index,
         mode} alone (see repro.core.workload.run_crashpoint),
      8. self-healing gate (membership): a seeded kill-then-replace cycle
         — kill a voter hard, join a learner, auto-promote it once run
         shipping catches it up, retire the dead id — ends with zero
         history violations, a restored 3-voter quorum, byte-equal scans
         across the final voter set and nonzero learner catch-up bytes
         on the wire (Metrics.on_ship); plus a 32-point fleet kill -9
         sweep across the config-change commit window that always
         recovers to ONE committed config with no acked-write loss and
         never two leaders for one term,
      9. tracing gate (fig_trace at smoke scale): a traced chaos run
         (leader kill + lossy window) audits to ZERO causality
         violations (durable-before-ack, quorum-before-commit,
         commit-before-apply, apply-before-client-ack checked
         structurally on the span/event stream); every synced nezha put
         carries EXACTLY one value-log fsync on the leader critical
         path; and the disabled tracer is free — the untraced same-seed
         run has the identical SimNet trace and Metrics, within noise
         on wall clock,
     10. sharding gate (fig_shard at smoke scale): N=4 range shards —
         each its own Raft group over one SimNet — scale put throughput
         >= 2x over the 1-shard fabric and monotonically 1 -> 2 -> 4
         (virtual ops per simulated second, seed-deterministic), the
         cross-shard scatter-gather scan is byte-equal to an unsharded
         reference store over identical data, and a seeded kill of ONE
         shard's leader leaves zero history violations while the other
         shards keep serving.
    Returns 0 on pass, 1 on fail (wired into `make smoke` / pytest -m smoke).
    """
    from benchmarks import common
    n, vsize = 96, 1024
    wa = {}
    rows = []

    def show(name, us, derived):
        rows.append((name, us, derived))
        print(f"{name},{us:.2f},{derived}")

    print("name,us_per_call,derived")
    for engine in common.ENGINES:
        c = common.make_cluster(engine, gc_threshold=1 << 60)
        items = common.keys_values(n, vsize)
        dt, done = common.timed(c.put_many, items)
        m, eng = common.leader_metrics(c)
        wa[engine] = sum(v for k, v in m.write_bytes.items()
                         if k in common.VALUE_CATS) / max(eng.user_bytes, 1)
        show(f"smoke_put/{engine}", 1e6 * dt / done,
             f"value_writes_x={wa[engine]:.2f}")
        common.destroy(c)

    from benchmarks.fig12_batching import _make_sync_cluster
    fsyncs = {}
    for batch in (1, 32):
        c = _make_sync_cluster("nezha", batch)
        items = common.keys_values(64, vsize)
        dt, done = common.timed(c.put_many, items, window=64, batch=batch)
        fsyncs[batch] = sum(mm.fsyncs for mm in c.metrics)
        show(f"smoke_batch/nezha/b{batch}", 1e6 * dt / done,
             f"fsyncs={fsyncs[batch]}")
        common.destroy(c)

    # fig10 at smoke scale: multiple GC cycles, leveled evidence in derived
    from benchmarks import fig10_gc_impact
    gc_rows = fig10_gc_impact.run(engines=["nezha"], n=150, vsize=1024,
                                  gc_threshold=30 << 10)
    for name, us, derived in gc_rows:
        show(name.replace("fig10_gc", "smoke_gc"), us, derived)
    gc_stats = common.parse_derived(gc_rows[0][2])

    # fig_runship at smoke scale: leader-driven GC + follower adoption
    from benchmarks import fig_runship
    rs_rows = fig_runship.run(n=150, vsize=1024, gc_threshold=30 << 10)
    for name, us, derived in rs_rows:
        show(name.replace("fig_runship", "smoke_runship"), us, derived)
    rs = {name.split("/")[-1]: common.parse_derived(d)
          for name, _, d in rs_rows}

    # fig_reads at smoke scale: the consistency-tier ladder
    from benchmarks import fig_reads
    rd_rows = fig_reads.run(n_keys=120, n_gets=24, n_scans=12, sizes=(3,))
    for name, us, derived in rd_rows:
        show(name.replace("fig_reads", "smoke_reads"), us, derived)
    rd = {name.split("/", 1)[-1]: common.parse_derived(d)
          for name, _, d in rd_rows}

    # fig_tail at smoke scale: open-loop load through a leader kill, on
    # the virtual clock — seed-deterministic p99s, single attempt
    from benchmarks import fig_tail
    ch_rows = fig_tail.chaos_smoke()
    for name, us, derived in ch_rows:
        show(name, us, derived)
    ch = common.parse_derived(ch_rows[0][2])

    # crash-point durability gate: seeded 64-point kill -9 sweep + one
    # full-cluster (fleet power loss) restart at a torn point
    import tempfile
    from repro.core.faultfs import MODES
    from repro.core.workload import run_crashpoint, run_full_restart
    cp_total = cp_fail = 0
    with tempfile.TemporaryDirectory(prefix="smoke_cp_") as cpd:
        cp_ops = run_crashpoint(f"{cpd}/record", seed=23)["ops"]
        picks = sorted({(i * cp_ops) // 64 for i in range(64)})
        for i, k in enumerate(picks):
            r = run_crashpoint(f"{cpd}/p{k}", seed=23, crash_index=k,
                               mode=MODES[i % len(MODES)])
            cp_total += 1
            if not (r["crashed"] and r["recovered_ok"]):
                cp_fail += 1
        fr = run_full_restart(f"{cpd}/fleet", seed=23, crash_index=120,
                              mode="torn")
    show("smoke_crashpoints/sweep", 0,
         f"points={cp_total};failures={cp_fail};io_ops={cp_ops}")
    show("smoke_crashpoints/full_restart", 0,
         f"recovered_ok={int(fr['recovered_ok'])}"
         f";converged={int(fr['converged'])}"
         f";violations={len(fr['violations'])};audit={len(fr['audit'])}")

    # self-healing gate: seeded kill-then-replace cycle + a crash-point
    # sweep of the config-change commit window
    from repro.core.cluster import Cluster
    from repro.core.workload import (OpRecord, check_history,
                                     run_membership_crashpoint)
    with tempfile.TemporaryDirectory(prefix="smoke_heal_") as hd:
        hc = Cluster(n=3, engine="nezha", workdir=f"{hd}/c", seed=31,
                     engine_kwargs={"gc_threshold": 4096})
        hc.elect()
        heal_hist = []
        for i in range(40):
            k, v = b"hk%06d" % i, b"hv%06d" % i
            hc.put(k, v)
            heal_hist.append(OpRecord("put", k, v))
        hc.force_gc()
        hc.drain_shipping(2000)
        ship0 = sum(m.total_ship_bytes() for m in hc.metrics)
        hc.crash(1)                      # kill a voter hard
        new = hc.replace_node(1)         # learner join -> promote -> retire
        for i in range(40, 56):
            k, v = b"hk%06d" % i, b"hv%06d" % i
            hc.put(k, v)
            heal_hist.append(OpRecord("put", k, v))
        got = hc.scan(b"hk", b"hl")
        heal_hist.append(OpRecord("scan", value=got, lo=b"hk", hi=b"hl"))
        heal_viol = check_history(heal_hist)
        hl = hc.leader()
        heal_voters = sorted(hl.voters)
        for _ in range(8000):            # settle applies, then compare
            if all(hc.nodes[i].last_applied >= hl.commit_index
                   for i in heal_voters):
                break
            hc.tick()
        heal_scans = [hc.engines[i].scan(b"hk", b"hl") for i in heal_voters]
        heal_equal = all(s == heal_scans[0] for s in heal_scans[1:])
        heal_ship = sum(m.total_ship_bytes() for m in hc.metrics) - ship0
        for e in hc.engines:
            if e is not None:
                e.close()
    hm_total = hm_fail = 0
    with tempfile.TemporaryDirectory(prefix="smoke_heal_cp_") as hpd:
        hrec = run_membership_crashpoint(f"{hpd}/record", seed=31)
        mlo, mhi = hrec["member_window"]
        for k in range(32):
            r = run_membership_crashpoint(
                f"{hpd}/p{k}", seed=31,
                crash_index=mlo + (mhi - mlo) * k // 32,
                mode=("torn", "drop")[k % 2])
            hm_total += 1
            if not (r["crashed"] and r["recovered_ok"]):
                hm_fail += 1
    show("smoke_heal/replace_cycle", 0,
         f"violations={len(heal_viol)};voters={len(heal_voters)}"
         f";removed_absent={int(1 not in heal_voters)}"
         f";scan_equal={int(heal_equal)};ship_bytes={heal_ship}")
    show("smoke_heal/config_window_sweep", 0,
         f"points={hm_total};failures={hm_fail}"
         f";window={mlo}-{mhi}")

    # tracing gate: causality audit + put critical path + zero-cost-off
    from benchmarks import fig_trace
    tr_rows = fig_trace.smoke_gate()
    for name, us, derived in tr_rows:
        show(name, us, derived)
    tr = {name.split("/", 1)[-1]: common.parse_derived(d)
          for name, _, d in tr_rows}

    # sharding gate: multi-Raft scaling + scatter-gather + per-group chaos
    from benchmarks import fig_shard
    sh_rows = fig_shard.smoke_gate()
    for name, us, derived in sh_rows:
        show(name, us, derived)
    sh = {name.split("/", 1)[-1]: common.parse_derived(d)
          for name, _, d in sh_rows}

    ok = True
    if wa["nezha"] > wa["original"]:
        show("smoke/FAIL", 0, f"nezha_wa={wa['nezha']:.2f}_exceeds_"
             f"original={wa['original']:.2f}")
        ok = False
    if fsyncs[32] * 4 > fsyncs[1]:
        show("smoke/FAIL", 0, f"batch32_fsyncs={fsyncs[32]}_not_under_"
             f"quarter_of_batch1={fsyncs[1]}")
        ok = False
    if gc_stats.get("gc_cycles", 0) < 2:
        show("smoke/FAIL", 0, f"leveled_gc_never_cycled={gc_stats}")
        ok = False
    if gc_stats.get("gc_flush_last", 0) > \
            2.5 * max(gc_stats.get("gc_flush_first", 0), 1):
        show("smoke/FAIL", 0, "gc_flush_cost_grew_with_store_size="
             f"{gc_stats.get('gc_flush_first')}->"
             f"{gc_stats.get('gc_flush_last')}")
        ok = False
    if rs["shipped"].get("scan_equal") != 1:
        show("smoke/FAIL", 0, "run_shipping_follower_scan_diverged")
        ok = False
    if rs["shipped"].get("follower_gc_flush_bytes", 1) > 0:
        show("smoke/FAIL", 0, "run_shipping_follower_still_flushed_"
             f"{rs['shipped'].get('follower_gc_flush_bytes'):.0f}_bytes")
        ok = False
    if rs["shipped"].get("cluster_gc_bytes", 1) >= \
            rs["local"].get("cluster_gc_bytes", 0):
        show("smoke/FAIL", 0, "run_shipping_did_not_cut_cluster_gc_bytes="
             f"{rs['shipped'].get('cluster_gc_bytes'):.0f}_vs_local="
             f"{rs['local'].get('cluster_gc_bytes'):.0f}")
        ok = False
    if rd["lease"].get("quorum_rounds", 1) != 0:
        show("smoke/FAIL", 0, "lease_reads_paid_quorum_rounds="
             f"{rd['lease'].get('quorum_rounds', 1):.0f}"
             "_under_stable_leader")
        ok = False
    if rd["n3/session_spread"].get("scan_equal") != 1:
        show("smoke/FAIL", 0, "session_follower_scan_diverged_from_leader")
        ok = False
    if rd["n3/session_spread"].get("follower_serves", 0) <= 0:
        show("smoke/FAIL", 0, "session_reads_never_served_by_a_follower")
        ok = False
    if ch.get("violations", 1) != 0:
        show("smoke/FAIL", 0, "chaos_run_violated_consistency_x"
             f"{ch.get('violations', 1):.0f}")
        ok = False
    if ch.get("faults", 0) < 2:
        show("smoke/FAIL", 0, "chaos_schedule_did_not_fire_both_faults="
             f"{ch.get('faults', 0):.0f}")
        ok = False
    if ch.get("p99_ratio", 99) > 10:
        show("smoke/FAIL", 0, "post_failover_p99_unbounded_ratio="
             f"{ch.get('p99_ratio', 99):.2f}_steady="
             f"{ch.get('steady_p99_us', 0):.0f}us_recovered="
             f"{ch.get('recovered_p99_us', 0):.0f}us")
        ok = False
    if cp_fail:
        show("smoke/FAIL", 0, "crashpoint_sweep_lost_acked_state_at_"
             f"{cp_fail}_of_{cp_total}_points_seed23")
        ok = False
    if not fr["recovered_ok"]:
        show("smoke/FAIL", 0, "full_cluster_restart_diverged_converged="
             f"{int(fr['converged'])}_violations={len(fr['violations'])}"
             f"_audit={len(fr['audit'])}")
        ok = False
    if heal_viol or heal_voters != [0, 2, new] or not heal_equal \
            or heal_ship <= 0:
        show("smoke/FAIL", 0, "replace_cycle_violations="
             f"{len(heal_viol)}_voters={heal_voters}"
             f"_scan_equal={int(heal_equal)}_ship_bytes={heal_ship}")
        ok = False
    if hm_fail:
        show("smoke/FAIL", 0, "config_window_sweep_failed_at_"
             f"{hm_fail}_of_{hm_total}_points_seed31")
        ok = False
    if tr["chaos_audit"].get("causality_violations", 1) != 0:
        show("smoke/FAIL", 0, "traced_chaos_run_broke_causality_x"
             f"{tr['chaos_audit'].get('causality_violations', 1):.0f}")
        ok = False
    if tr["put_critical_path"].get("vlog_fsyncs_min", 0) != 1 or \
            tr["put_critical_path"].get("vlog_fsyncs_max", 0) != 1:
        show("smoke/FAIL", 0, "put_critical_path_not_one_vlog_fsync="
             f"{tr['put_critical_path'].get('vlog_fsyncs_min')}-"
             f"{tr['put_critical_path'].get('vlog_fsyncs_max')}")
        ok = False
    if tr["disabled_footprint"].get("sim_identical") != 1:
        show("smoke/FAIL", 0, "tracer_install_perturbed_the_simulation")
        ok = False
    if tr["disabled_footprint"].get("wall_ratio", 99) > 2.5:
        show("smoke/FAIL", 0, "tracing_overhead_unbounded_wall_ratio="
             f"{tr['disabled_footprint'].get('wall_ratio', 99):.2f}")
        ok = False
    if sh["shards=4"].get("scaling_x", 0) < 2.0:
        show("smoke/FAIL", 0, "sharding_4x_fabric_scaled_puts_only_"
             f"{sh['shards=4'].get('scaling_x', 0):.2f}x_over_1_shard")
        ok = False
    if not (sh["shards=1"].get("vops_s", 0)
            < sh["shards=2"].get("vops_s", 0)
            < sh["shards=4"].get("vops_s", 0)):
        show("smoke/FAIL", 0, "shard_scaling_not_monotonic_vops="
             f"{sh['shards=1'].get('vops_s', 0):.0f}->"
             f"{sh['shards=2'].get('vops_s', 0):.0f}->"
             f"{sh['shards=4'].get('vops_s', 0):.0f}")
        ok = False
    if sh["scatter_gather"].get("scan_equal") != 1:
        show("smoke/FAIL", 0, "cross_shard_scan_diverged_from_unsharded_"
             "reference")
        ok = False
    if sh["kill_group1"].get("violations", 1) != 0 or \
            sh["kill_group1"].get("faults", 0) < 2:
        show("smoke/FAIL", 0, "one_shard_leader_kill_violations="
             f"{sh['kill_group1'].get('violations', 1):.0f}_faults="
             f"{sh['kill_group1'].get('faults', 0):.0f}")
        ok = False
    if ok:
        show("smoke/PASS", 0, f"nezha_wa={wa['nezha']:.2f}"
             f";original_wa={wa['original']:.2f}"
             f";fsync_cut={fsyncs[1]}->{fsyncs[32]}"
             f";gc_cycles={gc_stats.get('gc_cycles'):.0f}"
             f";gc_flush={gc_stats.get('gc_flush_first'):.0f}->"
             f"{gc_stats.get('gc_flush_last'):.0f}"
             f";runship_cluster_gc={rs['local'].get('cluster_gc_bytes'):.0f}"
             f"->{rs['shipped'].get('cluster_gc_bytes'):.0f}"
             f";lease_rounds={rd['lease'].get('quorum_rounds', 1):.0f}"
             f";session_scaling_x="
             f"{rd['n3/session_spread'].get('scaling_x', 0):.2f}"
             f";chaos_violations={ch.get('violations', 1):.0f}"
             f";chaos_p99_ratio={ch.get('p99_ratio', 99):.2f}"
             f";crashpoints={cp_total}_all_recovered"
             f";full_restart_ok={int(fr['recovered_ok'])}"
             f";heal_voters={len(heal_voters)}"
             f";heal_ship_bytes={heal_ship}"
             f";heal_crashpoints={hm_total}_all_recovered"
             f";trace_violations="
             f"{tr['chaos_audit'].get('causality_violations'):.0f}"
             f";trace_vlog_fsyncs_per_put=1"
             f";trace_wall_ratio="
             f"{tr['disabled_footprint'].get('wall_ratio'):.2f}"
             f";shard_scaling_x={sh['shards=4'].get('scaling_x', 0):.2f}"
             f";shard_scan_equal={sh['scatter_gather'].get('scan_equal'):.0f}"
             f";shard_chaos_violations="
             f"{sh['kill_group1'].get('violations'):.0f}")
    common.write_artifact("smoke", rows)
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated subset: fig4..fig12, fig_*, or "
                         "roofline (needs results/dryrun.json; never run "
                         "by default)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny all-engine assertion run (CI gate)")
    args = ap.parse_args()
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        return smoke()

    from benchmarks import (common, fig4_put, fig5_get, fig6_scan,
                            fig7_scan_length, fig8_ycsb, fig9_scalability,
                            fig10_gc_impact, fig11_recovery, fig12_batching,
                            fig_reads, fig_runship, fig_shard, fig_tail,
                            fig_trace, roofline)

    suites = {
        "fig4": lambda: fig4_put.run()[0],
        "fig5": fig5_get.run,
        "fig6": fig6_scan.run,
        "fig7": fig7_scan_length.run,
        "fig8": fig8_ycsb.run,
        "fig9": fig9_scalability.run,
        "fig10": fig10_gc_impact.run,
        "fig11": fig11_recovery.run,
        "fig12": fig12_batching.run,
        "fig_reads": fig_reads.run,
        "fig_runship": fig_runship.run,
        "fig_shard": fig_shard.run,
        "fig_tail": fig_tail.run,
        "fig_trace": fig_trace.run,
    }
    on_request = {"roofline": roofline.run}
    only = [n for n in args.only.split(",") if n]
    unknown = sorted(set(only) - set(suites) - set(on_request))
    if unknown:
        ap.error(f"unknown suite(s): {','.join(unknown)}")
    chosen = only or list(suites)
    print("name,us_per_call,derived")
    t0 = time.time()
    failed = []
    for name in chosen:
        fn = suites.get(name) or on_request[name]
        t1 = time.time()
        try:
            rows = fn()
            common.emit(rows)
            path = common.write_artifact(name, rows)
            print(f"# wrote {path}", file=sys.stderr)
        except Exception as e:  # a failed suite must not hide the others
            print(f"{name}/SUITE_ERROR,0,{e!r}")
            traceback.print_exc()
            failed.append(name)
        print(f"# {name} done in {time.time() - t1:.1f}s", file=sys.stderr)
    print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)
    if failed:
        print(f"# failed suites: {','.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
