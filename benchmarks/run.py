"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus context lines prefixed
with '#').  Mapping to the paper:

  overhead_*   -> Table 2 columns O/H (tracer overhead on a real training
                  loop), CR (critical ratio), M (profiler memory), PPT
                  (post-processing time)
  cmetric_*    -> the "extremely low overhead" claim: per-event probe cost
                  and offline fold throughput for every backend
  balance_*    -> Figures 4/5: per-worker CMetric imbalance detection and
                  the effect of rebalancing (Ferret thread-reallocation
                  experiment, transplanted to pipeline stages)
  detect_*     -> §5.2: injected-bottleneck identification accuracy
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def smoke_detect(n_slices: int, out: str) -> dict:
    """CI smoke target: the detection-stage scaling benchmark on a synthetic
    10^5-critical-slice table, persisted as JSON so successive PRs leave a
    perf trajectory (``python -m benchmarks.run --smoke detect``)."""
    from benchmarks import bench_detect
    res = bench_detect.run_scale(n_slices)
    res["n_slices_requested"] = n_slices
    res["timestamp"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    with open(out, "w") as f:
        json.dump(res, f, indent=2)
    print(f"# detection stage @ {res['n_critical']} critical slices: "
          f"seed loop {res['seed_loop_s'] * 1e3:.1f} ms, columnar "
          f"{res['table_s'] * 1e3:.1f} ms "
          f"({res['speedup']:.1f}x) -> {out}")
    return res


def smoke_probe(pairs: int, threads: int, out: str) -> dict:
    """CI smoke target: per-event probe cost, sharded lock-free hot path vs
    the retained locked seed body, single-thread and contended
    (``python -m benchmarks.run --smoke probe`` -> BENCH_probe.json)."""
    from benchmarks import bench_probe
    res = bench_probe.run_probe(pairs=pairs, threads=threads)
    res["timestamp"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    with open(out, "w") as f:
        json.dump(res, f, indent=2)
    print("# probe hot path: locked "
          f"{res['locked_us_per_event_1t']:.2f}us/ev 1t "
          f"/ {res['locked_us_per_event_mt']:.2f}us/ev {threads}t, sharded "
          f"{res['sharded_us_per_event_1t']:.2f}us/ev 1t "
          f"/ {res['sharded_us_per_event_mt']:.2f}us/ev {threads}t "
          f"-> {res['speedup_1t']:.1f}x single, {res['speedup_mt']:.1f}x "
          f"contended -> {out}")
    return res


def smoke_session(threads: int, out: str) -> dict:
    """Streaming-session smoke: live capture throughput with the background
    drain+fold worker, mid-capture snapshot latency, and the disk-spill
    store's cost (``python -m benchmarks.run --smoke session``)."""
    from benchmarks import bench_session
    res = bench_session.run_session(threads=threads)
    res["timestamp"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    with open(out, "w") as f:
        json.dump(res, f, indent=2)
    print(f"# streaming session: {res['ram_events_per_s']:.0f} ev/s live "
          f"(snapshot {res['ram_snapshot_ms']:.1f} ms mid-capture), "
          f"{res['spill_events_per_s']:.0f} ev/s spilling "
          f"(resident <= {res['spill_max_resident_rows']} rows, "
          f"{res['spill_slowdown']:.2f}x slowdown), capped snapshot "
          f"{res['capped_snapshot_ms']:.1f} ms @ budget "
          f"{res['max_rows_per_sync']} -> {out}")
    return res


def smoke_fleet(producers: int, out: str) -> dict:
    """Fleet-ingest smoke: localhost loopback, N producer sessions
    streaming compressed frames over real sockets — with durable journals
    on both ends — into one IngestServer+FleetSource session
    (``python -m benchmarks.run --smoke fleet`` -> BENCH_fleet.json).
    GATED in CI: losslessness (zero lost/duplicate chunks) and
    ingest-vs-offline equality are asserted inside the benchmark, so any
    regression fails the run instead of printing a warning."""
    from benchmarks import bench_fleet
    res = bench_fleet.run_fleet(producers=producers)
    res["timestamp"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    with open(out, "w") as f:
        json.dump(res, f, indent=2)
    print(f"# fleet ingest: {res['producers']} producers, "
          f"{res['ingest_events_per_s']:.0f} ev/s over loopback "
          f"({res['wire_compression_ratio']:.1f}x wire compression), "
          f"final report {res['final_report_ms']:.1f} ms, "
          f"lossless={res['lossless']} "
          f"offline_equal={res['offline_equal']} -> {out}")
    return res


def smoke_chaos(producers: int, out: str) -> dict:
    """Chaos smoke: N journaled producers stream through a seeded
    FaultPlan (producer kills, server kill/restarts, partitions, slow
    hosts) while the recovery gates assert bit-equal journals and exact
    chunk reconciliation (``python -m benchmarks.run --smoke chaos`` ->
    BENCH_chaos.json).  GATED inside the benchmark: any lost chunk,
    duplicate fold, or recovered-vs-oracle drift raises."""
    from benchmarks import bench_chaos
    res = bench_chaos.run_chaos(producers=producers)
    res["timestamp"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    with open(out, "w") as f:
        json.dump(res, f, indent=2)
    print(f"# chaos: {res['producers']} producers, "
          f"{res['producer_kills']} kills / "
          f"{res['server_restarts']} server restarts / "
          f"{res['partitions']} partitions in {res['wall_s']:.1f}s — "
          f"lost={res['lost_chunks']} dup={res['duplicate_chunks']} "
          f"shed={res['shed_chunks']} "
          f"recovery_equal={res['recovery_equal']} -> {out}")
    return res


def smoke_service(producers: int, out: str) -> dict:
    """Service smoke: the live HTTP query API (ProfilerService) over a
    journaled 2-producer ingest — endpoint latency plus GATED contracts:
    /api/report byte-equal to export("json"), windowed /api/top entries
    from the journal re-fold, /metrics exposition families, /api/hosts
    roster (``python -m benchmarks.run --smoke service`` ->
    BENCH_service.json)."""
    from benchmarks import bench_service
    res = bench_service.run_service(producers=producers)
    res["timestamp"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    with open(out, "w") as f:
        json.dump(res, f, indent=2)
    print(f"# service: /api/report {res['report_ms']:.2f} ms "
          f"({res['report_bytes']} B, equal={res['report_equal']}), "
          f"/api/top?window {res['top_window_ms']:.2f} ms "
          f"({res['top_entries']} entries), /metrics "
          f"{res['metrics_ms']:.2f} ms -> {out}")
    return res


def smoke_whatif(out: str) -> dict:
    """What-if accuracy smoke: counterfactual projections checked against
    constructible ground truth — MoE hot-expert removal and an injected
    serial optimizer step, both with known true gains, plus /api/whatif
    byte-consistency with the offline engine (``python -m benchmarks.run
    --smoke whatif`` -> BENCH_whatif.json).  GATED inside the benchmark:
    projected-vs-measured relative error above 15% or a wire/offline
    byte mismatch raises."""
    from benchmarks import bench_whatif
    res = bench_whatif.run_whatif()
    res["timestamp"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    with open(out, "w") as f:
        json.dump(res, f, indent=2)
    print(f"# whatif: moe projected {res['moe_projected_speedup']:.3f}x vs "
          f"measured {res['moe_actual_speedup']:.3f}x "
          f"(err {res['moe_rel_err'] * 100:.1f}%), pipeline err "
          f"{res['pipeline_rel_err'] * 100:.1f}%, service byte_equal="
          f"{res['service_byte_equal']} "
          f"({res['service_whatif_ms']:.2f} ms) -> {out}")
    return res


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", choices=["detect", "probe", "session",
                                        "fleet", "chaos", "service",
                                        "whatif"],
                    help="run one fast smoke benchmark and write a JSON "
                         "artifact instead of the full CSV harness")
    ap.add_argument("--producers", type=int, default=2,
                    help="producer sessions for --smoke fleet")
    ap.add_argument("--chaos-producers", type=int, default=64,
                    help="producer sessions for --smoke chaos")
    ap.add_argument("--n-slices", type=int, default=250_000,
                    help="table size for --smoke detect (~43%% of rows land "
                         "under n_min, so the default yields >=1e5 critical "
                         "slices)")
    ap.add_argument("--pairs", type=int, default=20_000,
                    help="begin/end pairs per worker for --smoke probe")
    ap.add_argument("--threads", type=int, default=4,
                    help="contending workers for --smoke probe")
    ap.add_argument("--out", default=None,
                    help="JSON artifact path (default BENCH_<smoke>.json)")
    args = ap.parse_args()
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    if args.smoke == "detect":
        smoke_detect(args.n_slices, args.out or "BENCH_detect.json")
        return
    if args.smoke == "probe":
        smoke_probe(args.pairs, args.threads, args.out or "BENCH_probe.json")
        return
    if args.smoke == "session":
        smoke_session(args.threads, args.out or "BENCH_session.json")
        return
    if args.smoke == "fleet":
        smoke_fleet(args.producers, args.out or "BENCH_fleet.json")
        return
    if args.smoke == "chaos":
        smoke_chaos(args.chaos_producers, args.out or "BENCH_chaos.json")
        return
    if args.smoke == "service":
        smoke_service(args.producers, args.out or "BENCH_service.json")
        return
    if args.smoke == "whatif":
        smoke_whatif(args.out or "BENCH_whatif.json")
        return

    from benchmarks import (bench_balance, bench_cmetric, bench_detect,
                            bench_overhead, bench_probe)
    print("# GAPP benchmark harness — paper-table analogues")
    print("name,us_per_call,derived")
    for mod in (bench_probe, bench_cmetric, bench_overhead, bench_balance,
                bench_detect):
        t0 = time.time()
        for row in mod.run():
            name, us, derived = row
            print(f"{name},{us:.3f},{derived}", flush=True)
        print(f"# {mod.__name__} done in {time.time() - t0:.1f}s",
              file=sys.stderr)


if __name__ == "__main__":
    main()
