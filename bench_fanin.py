"""Fan-in microbench: N simulated workers hammering one PS shard.

The blocking core serves N concurrent window-delta pushes with N
caller/server threads convoying on the shard lock, and pays the full
serial chain PER REPORT: decode (top-k densify / int8 dequant), vector
apply, merged-slice copy, response serialization. The async master
core (EDL_DISPATCH=loop, rpc/dispatch.py) plus hierarchical fan-in
combining (--fanin_combine, master/fanin.py) batches every rendezvoused
cohort of k compatible pushes into ONE lock acquisition, ONE apply, ONE
merged-slice copy and ONE shared pre-packed response; sparse (top-k)
members additionally skip densification entirely — the presum
scatter-adds just the k shipped entries per member, so the per-report
cost scales with the compression ratio instead of the slice length.

Protocol: one `PSShardServicer` (no optimizer — the delta path is pure
vector add) behind a real `RpcServer`; N worker threads, each with its
own `RpcClient`, push `PSPushDelta` in a closed loop. Requests are
PRE-PACKED once per worker (`messages.Prepacked`) and keyless with a
constant base_version — standard load-generator practice: the bench
measures SERVER fan-in capacity, so per-call client pack cost is taken
off the table, skipping dedup bookkeeping is protocol-legal for
keyless pushes, and a constant base is protocol-legal because the
response always carries the merged slice when the base fell behind
(dedup/fencing/exactness under faults are the chaos e2e suite's job,
not the bench's). Delta values are exactly representable in f32
(2^-12), so the final vector is bit-identical however the combine
stage batches. After an untimed warm-up, a fixed timed window is
measured; only calls that COMPLETE inside the window count. Every cell
asserts version == applied_pushes (no report lost or double-applied).

Grid: wire in {f32 (dense 4 MB slice), topk (1% top-k sparse over the
same slice)} x N in {8, 64, 256} x tier x core in {blocking (threads
dispatch, no combine), loop_combine}. The inproc tier runs both wires;
the uds tier runs ONLY the topk wire — shipping dense 4 MB frames
through a socket measures memcpy throughput, not dispatch (both cores
bottleneck on moving the same bytes), and the compressed wire tier
exists precisely because raw bytes are the socket-path bottleneck
(see docs/performance.md). The acceptance
bar is the N=256 speedup of loop_combine over blocking on the same
machine (>= 4x on the best cell; the top-k cell is the headline — that
is the wire form fan-in-at-scale deployments ship).

Prints ONE JSON line; also importable (`run_suite`,
tests/test_fanin_bench.py).
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

DEFAULT_NS = (8, 64, 256)
#: tier -> wire forms benched on it (module docstring: dense frames
#: over a socket measure memcpy, not dispatch, so uds runs topk only)
DEFAULT_GRID = (
    ("inproc", ("f32", "topk")),
    ("uds", ("topk",)),
)
DEFAULT_SLICE = 1 << 20  # 4 MB of f32 per report — a realistic PS slice
TOPK_DENSITY = 0.01
#: exactly representable in f32 at any summation order/grouping, so the
#: final vector is bit-identical however the combine stage batches
DELTA_VALUE = 2.0**-12


def _make_request(wire: str, slice_len: int, wid: int):
    """One worker's pre-packed PSPushDelta request (docstring)."""
    from elasticdl_tpu.common import codec, messages

    if wire == "topk":
        # each worker ships its own top-k support, as real sparsified
        # reports would (deterministic per worker id)
        rng = np.random.default_rng(wid)
        k = max(1, int(slice_len * TOPK_DENSITY))
        idx = np.sort(rng.choice(slice_len, size=k, replace=False))
        delta = codec.SparseDelta(
            indices=idx.astype(np.int64),
            values=np.full(k, DELTA_VALUE, dtype=np.float32),
            n=slice_len,
        )
    else:
        delta = np.full(slice_len, DELTA_VALUE, dtype=np.float32)
    return messages.Prepacked(
        messages.pack(
            {"delta": delta, "steps": 1, "base_version": 0, "epoch": 0}
        )
    )


def _worker_loop(
    endpoint: str,
    request,
    stop: threading.Event,
    records: List[Tuple[float, float]],
    errors: List[BaseException],
):
    """Closed-loop pusher: one in-flight PSPushDelta per worker.
    Appends (completion_time, call_seconds) per call."""
    from elasticdl_tpu.rpc.client import RpcClient

    try:
        cli = RpcClient(endpoint)
        while not stop.is_set():
            t0 = time.perf_counter()
            cli.call("PSPushDelta", request)
            t1 = time.perf_counter()
            records.append((t1, t1 - t0))
    except BaseException as e:  # surfaced by the cell runner
        errors.append(e)


def run_cell(
    n_workers: int,
    tier: str,
    *,
    dispatch: str,
    combine: bool,
    wire: str = "f32",
    slice_len: int = DEFAULT_SLICE,
    warmup_s: float = 0.5,
    window_s: float = 2.0,
) -> Dict:
    """One grid cell: returns sustained reports/sec + latency + ratio."""
    from elasticdl_tpu.common.constants import ENV_DISPATCH, ENV_TRANSPORT
    from elasticdl_tpu.master.ps_shard import PSShardServicer
    from elasticdl_tpu.rpc.client import RpcClient
    from elasticdl_tpu.rpc.server import RpcServer

    prev = {k: os.environ.get(k) for k in (ENV_DISPATCH, ENV_TRANSPORT)}
    os.environ[ENV_DISPATCH] = dispatch
    os.environ[ENV_TRANSPORT] = tier
    try:
        servicer = PSShardServicer(0, 1, fanin_combine=combine)
        server = RpcServer(servicer.handlers(), port=0)
        servicer.attach_wire_stats(server.wire)
        server.start()
        endpoint = f"localhost:{server.port}"
        init = RpcClient(endpoint)
        init.call(
            "PSInit",
            {"vec": np.zeros(slice_len, np.float32), "version": 0, "epoch": 0},
        )

        stop = threading.Event()
        per_worker: List[List[Tuple[float, float]]] = [
            [] for _ in range(n_workers)
        ]
        errors: List[BaseException] = []
        threads = [
            threading.Thread(
                target=_worker_loop,
                args=(
                    endpoint,
                    _make_request(wire, slice_len, i),
                    stop,
                    per_worker[i],
                    errors,
                ),
                daemon=True,
            )
            for i in range(n_workers)
        ]
        for t in threads:
            t.start()
        time.sleep(warmup_s)
        t0 = time.perf_counter()
        time.sleep(window_s)
        t1 = time.perf_counter()
        stop.set()
        for t in threads:
            t.join(timeout=120)
        if errors:
            raise errors[0]

        in_window = [
            dt
            for recs in per_worker
            for (done, dt) in recs
            if t0 <= done <= t1
        ]
        stats = servicer.stats()
        version = stats["version"]
        # which tiers actually carried the cell
        transports = server.wire_stats().get("transports", {})
    finally:
        try:
            server.stop()
        except Exception:
            pass
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    count = len(in_window)
    batches = stats["combined_batches"]
    return {
        "n_workers": n_workers,
        "tier": tier,
        "wire": wire,
        "core": "loop_combine" if combine else "blocking",
        "reports_per_sec": round(count / (t1 - t0), 1),
        "p50_ms": round(
            statistics.median(in_window) * 1000, 3
        ) if in_window else None,
        "p99_ms": round(
            statistics.quantiles(in_window, n=100)[98] * 1000, 3
        ) if len(in_window) >= 100 else None,
        "combine_ratio": round(
            stats["combined_reports"] / batches, 2
        ) if batches else 1.0,
        # exactness check rides every cell: version == applied pushes
        # (each push is steps=1), no report lost or double-applied
        "version": version,
        "applied_pushes": stats["applied_pushes"],
        "server_transports": transports,
    }


TREE_N = 64
TREE_H = 4


def _tree_request(wire: str, slice_len: int, wid: int, key: str):
    """One keyed AggPushDelta frame. Unlike the flat columns these
    cannot be keyless: the aggregator forwards the cohort's report_key
    list upstream (PS-side dedup/replay is the whole point of the
    protocol), so each call packs a fresh key. The pack cost is charged
    to the tree column — it must win anyway."""
    from elasticdl_tpu.common import codec, messages

    if wire == "topk":
        rng = np.random.default_rng(wid)
        k = max(1, int(slice_len * TOPK_DENSITY))
        idx = np.sort(rng.choice(slice_len, size=k, replace=False))
        delta = codec.SparseDelta(
            indices=idx.astype(np.int64),
            values=np.full(k, DELTA_VALUE, dtype=np.float32),
            n=slice_len,
        )
    else:
        delta = np.full(slice_len, DELTA_VALUE, dtype=np.float32)
    return messages.Prepacked(
        messages.pack(
            {
                "delta": delta,
                "steps": 1,
                "base_version": 0,
                "report_key": key,
                "shard": 0,
                "shard_epoch": 0,
                "epoch": 0,
            }
        )
    )


def _tree_worker_loop(
    endpoint: str,
    wire: str,
    slice_len: int,
    wid: int,
    stop: threading.Event,
    records: List[Tuple[float, float]],
    errors: List[BaseException],
):
    """Closed-loop keyed pusher against this worker's aggregator."""
    from elasticdl_tpu.rpc.client import RpcClient

    try:
        cli = RpcClient(endpoint)
        seq = 0
        while not stop.is_set():
            req = _tree_request(wire, slice_len, wid, f"b{wid}.{seq}")
            seq += 1
            t0 = time.perf_counter()
            cli.call("AggPushDelta", req)
            t1 = time.perf_counter()
            records.append((t1, t1 - t0))
    except BaseException as e:  # surfaced by the cell runner
        errors.append(e)


def run_tree_cell(
    n_workers: int = TREE_N,
    n_aggs: int = TREE_H,
    *,
    tier: str = "uds",
    upstream: str = "uds",
    wire: str = "topk",
    slice_len: int = DEFAULT_SLICE,
    warmup_s: float = 0.5,
    window_s: float = 2.0,
) -> Dict:
    """The aggregation-tree core (agg/): N workers spread over H
    host-local aggregator nodes, each presumming its rendezvoused
    cohort and forwarding ONE combined delta upstream — the master-side
    fan-in degree drops from #workers to #hosts.

    Topology mirrors production: the bench process hosts the worker
    fleet and the (inproc) PS shard in BOTH this cell and the flat
    comparator; the tree cell additionally spawns H REAL aggregator
    subprocesses (`AggGroup` process mode — the same entrypoint the
    master launches), so the member decode + presum + fan-back work
    that the flat core burns on the master's interpreter runs on the
    aggregator hosts' own CPUs, exactly the offload the tree buys in
    production. worker->aggregator rides `tier` (uds — the carrier a
    local peer gets), aggregator->PS is pinned to `upstream`
    (select_transport's per-link tier override). The PS runs the SAME
    loop+combine core as the flat comparator, so the delta is purely
    the tree.

    Two measurements per cell:
    - a synchronized fan-in round: every worker pushes exactly once
      with a long rendezvous linger; the PS must see exactly H
      PSPushDeltaCombined calls (one per aggregator) carrying all N
      report_keys — the degree-reduction contract, counted on the
      master's own wire stats;
    - the sustained closed-loop window, same protocol as the flat
      columns (only calls completing inside the window count), with
      version == applied_pushes exactness on every run.
    """
    import math

    from elasticdl_tpu.agg.group import AggGroup
    from elasticdl_tpu.common.constants import (
        ENV_AGG_BATCH,
        ENV_AGG_UPSTREAM_TIER,
        ENV_AGG_WAIT_MS,
        ENV_DISPATCH,
        ENV_TRANSPORT,
    )
    from elasticdl_tpu.master.ps_shard import PSShardServicer
    from elasticdl_tpu.rpc.client import RpcClient
    from elasticdl_tpu.rpc.server import RpcServer

    cohort = max(1, math.ceil(n_workers / n_aggs))
    env_keys = (
        ENV_DISPATCH,
        ENV_TRANSPORT,
        ENV_AGG_BATCH,
        ENV_AGG_WAIT_MS,
        ENV_AGG_UPSTREAM_TIER,
    )
    prev = {k: os.environ.get(k) for k in env_keys}
    os.environ[ENV_DISPATCH] = "loop"
    agg = None
    try:
        # the master-side endpoint serves every tier (auto) so the
        # aggregator subprocesses can reach it on the `upstream` socket
        os.environ[ENV_TRANSPORT] = "auto"
        ps = PSShardServicer(0, 1, fanin_combine=True)
        ps_server = RpcServer(ps.handlers(), port=0)
        ps.attach_wire_stats(ps_server.wire)
        ps_server.start()
        ps_endpoint = f"localhost:{ps_server.port}"
        ps.init_slice(
            {"vec": np.zeros(slice_len, np.float32), "version": 0}
        )

        # aggregator nodes inherit the knobs through the registered
        # env surface, like master-launched ones do
        os.environ[ENV_TRANSPORT] = tier
        os.environ[ENV_AGG_BATCH] = str(cohort)
        os.environ[ENV_AGG_WAIT_MS] = "250"
        os.environ[ENV_AGG_UPSTREAM_TIER] = upstream
        agg = AggGroup(n_aggs, [ps_endpoint], mode="process")
        agg.start()
        endpoints = list(agg.endpoints)

        # -- synchronized fan-in round: count upstream calls ---------
        sync_errors: List[BaseException] = []
        barrier = threading.Barrier(n_workers)

        def sync_push(wid: int):
            try:
                cli = RpcClient(endpoints[wid % n_aggs])
                cli.call("AggStats", {})  # warm the connection
                barrier.wait(timeout=60)
                cli.call(
                    "AggPushDelta",
                    _tree_request(wire, slice_len, wid, f"sync.w{wid}"),
                )
            except BaseException as e:
                sync_errors.append(e)

        sync_threads = [
            threading.Thread(target=sync_push, args=(w,), daemon=True)
            for w in range(n_workers)
        ]
        for t in sync_threads:
            t.start()
        for t in sync_threads:
            t.join(timeout=120)
        if sync_errors:
            raise sync_errors[0]
        sync_methods = ps_server.wire_stats().get("methods", {})
        sync_upstream_calls = sync_methods.get(
            "PSPushDeltaCombined", {}
        ).get("calls", 0)
        sync_single_calls = sync_methods.get("PSPushDelta", {}).get(
            "calls", 0
        )
        sync_version = ps.stats()["version"]

        # -- sustained closed-loop window ----------------------------
        stop = threading.Event()
        per_worker: List[List[Tuple[float, float]]] = [
            [] for _ in range(n_workers)
        ]
        errors: List[BaseException] = []
        threads = [
            threading.Thread(
                target=_tree_worker_loop,
                args=(
                    endpoints[i % n_aggs],
                    wire,
                    slice_len,
                    i,
                    stop,
                    per_worker[i],
                    errors,
                ),
                daemon=True,
            )
            for i in range(n_workers)
        ]
        for t in threads:
            t.start()
        time.sleep(warmup_s)
        t0 = time.perf_counter()
        time.sleep(window_s)
        t1 = time.perf_counter()
        stop.set()
        for t in threads:
            t.join(timeout=120)
        if errors:
            raise errors[0]

        in_window = [
            dt
            for recs in per_worker
            for (done, dt) in recs
            if t0 <= done <= t1
        ]
        ps_stats = ps.stats()
        # node-side accounting over the wire (the nodes are real
        # subprocesses, like master-launched ones)
        agg_stats = [
            RpcClient(ep).call("AggStats", {}) for ep in endpoints
        ]
        ps_transports = ps_server.wire_stats().get("transports", {})
        agg_transports: Dict[str, Dict[str, int]] = {}
        for st in agg_stats:
            for t_name, row in (st.get("transports") or {}).items():
                total = agg_transports.setdefault(
                    t_name,
                    {"bytes_sent": 0, "bytes_received": 0, "calls": 0},
                )
                for k in total:
                    total[k] += row.get(k, 0)
    finally:
        if agg is not None:
            try:
                agg.stop()
            except Exception:
                pass
        try:
            ps_server.stop()
        except Exception:
            pass
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    count = len(in_window)
    cohorts = sum(s["cohorts_forwarded"] for s in agg_stats)
    members = sum(s["members_in"] for s in agg_stats)
    return {
        "n_workers": n_workers,
        "n_aggs": n_aggs,
        "tier": tier,
        "upstream_tier": upstream,
        "wire": wire,
        "core": "tree",
        "reports_per_sec": round(count / (t1 - t0), 1),
        "p50_ms": round(
            statistics.median(in_window) * 1000, 3
        ) if in_window else None,
        "p99_ms": round(
            statistics.quantiles(in_window, n=100)[98] * 1000, 3
        ) if len(in_window) >= 100 else None,
        # degree reduction, counted on the master's own wire stats:
        # one synchronized all-worker round lands as exactly H combined
        # upstream calls (and zero serial singles)
        "sync_round": {
            "upstream_combined_calls": sync_upstream_calls,
            "upstream_single_calls": sync_single_calls,
            "version": sync_version,
        },
        "combine_ratio": round(members / cohorts, 2) if cohorts else 1.0,
        "version": ps_stats["version"],
        "applied_pushes": ps_stats["applied_pushes"],
        "cohorts_forwarded": cohorts,
        "singles_forwarded": sum(s["singles_forwarded"] for s in agg_stats),
        "decompositions": sum(s["decompositions"] for s in agg_stats),
        "upstream_errors": sum(s["upstream_errors"] for s in agg_stats),
        "ps_transports": ps_transports,
        "agg_transports": agg_transports,
    }


def run_suite(
    ns=DEFAULT_NS,
    grid=DEFAULT_GRID,
    *,
    slice_len: int = DEFAULT_SLICE,
    warmup_s: float = 0.5,
    window_s: float = 2.0,
    tree_cell: Optional[Tuple[int, int]] = (TREE_N, TREE_H),
) -> Dict:
    """Full before/after grid + the N=max speedup per (tier, wire),
    plus the aggregation-tree column (`tree_cell` = (N workers,
    H aggregators); None skips it)."""
    cells: Dict[str, Dict[str, Dict[str, Dict]]] = {}
    for tier, wires in grid:
        cells[tier] = {}
        for wire in wires:
            cells[tier][wire] = {}
            for n in ns:
                before = run_cell(
                    n, tier, dispatch="threads", combine=False, wire=wire,
                    slice_len=slice_len, warmup_s=warmup_s,
                    window_s=window_s,
                )
                after = run_cell(
                    n, tier, dispatch="loop", combine=True, wire=wire,
                    slice_len=slice_len, warmup_s=warmup_s,
                    window_s=window_s,
                )
                assert before["version"] == before["applied_pushes"]
                assert after["version"] == after["applied_pushes"]
                speedup = round(
                    after["reports_per_sec"]
                    / max(1e-9, before["reports_per_sec"]),
                    2,
                )
                cells[tier][wire][str(n)] = {
                    "blocking": before,
                    "loop_combine": after,
                    "speedup": speedup,
                }
                print(
                    f"bench_fanin[{tier} {wire} N={n}]: blocking "
                    f"{before['reports_per_sec']:.0f} rep/s "
                    f"(p99 {before['p99_ms']} ms) -> loop+combine "
                    f"{after['reports_per_sec']:.0f} rep/s "
                    f"(p99 {after['p99_ms']} ms, ratio "
                    f"{after['combine_ratio']}) = {speedup}x",
                    file=sys.stderr,
                )
    # -- the aggregation-tree column (agg/): N workers through H
    # host-local presum nodes vs the SAME N direct on the best flat
    # core (loop+combine) over the same worker-visible tier ----------
    tree = None
    if tree_cell:
        n, h = tree_cell
        flat = run_cell(
            n, "uds", dispatch="loop", combine=True, wire="topk",
            slice_len=slice_len, warmup_s=warmup_s, window_s=window_s,
        )
        cell = run_tree_cell(
            n, h, tier="uds", upstream="uds", wire="topk",
            slice_len=slice_len, warmup_s=warmup_s, window_s=window_s,
        )
        assert flat["version"] == flat["applied_pushes"]
        assert cell["version"] == cell["applied_pushes"]
        tree_speedup = round(
            cell["reports_per_sec"]
            / max(1e-9, flat["reports_per_sec"]),
            2,
        )
        tree = {
            "tree": cell,
            "flat_loop_combine": flat,
            "speedup": tree_speedup,
        }
        print(
            f"bench_fanin[tree N={n} H={h}]: flat loop+combine "
            f"{flat['reports_per_sec']:.0f} rep/s -> tree "
            f"{cell['reports_per_sec']:.0f} rep/s = {tree_speedup}x; "
            f"sync round saw "
            f"{cell['sync_round']['upstream_combined_calls']} upstream "
            f"calls for {n} reports",
            file=sys.stderr,
        )
    n_max = str(max(ns))
    speedups = {
        f"{tier}/{wire}": cells[tier][wire][n_max]["speedup"]
        for tier, wires in grid
        for wire in wires
    }
    headline = max(speedups, key=speedups.get)
    return {
        "metric": "fanin_reports_per_sec_speedup",
        "slice_len": slice_len,
        "topk_density": TOPK_DENSITY,
        "window_s": window_s,
        "cells": cells,
        "tree": tree,
        "speedup_at_max_n": speedups,
        "headline_cell": headline,
        "value": speedups[headline],
        "protocol": (
            "N closed-loop pusher threads vs one PS shard; sustained "
            "PSPushDelta reports/sec over a fixed timed window (only "
            "calls completing inside it count), p50/p99 per-call "
            "latency, servicer-measured combine ratio. Requests are "
            "pre-packed and keyless with a constant base (server-"
            "capacity measurement; see module docstring). blocking = "
            "threads dispatch, no combining (thread-per-request core); "
            "loop_combine = EDL_DISPATCH=loop event-loop core + "
            "hierarchical fan-in combining. speedup_at_max_n is per "
            "(tier, wire); value is the best cell at N=256 and the "
            "acceptance number (>= 4x)"
        ),
    }


def main(argv: Optional[List[str]] = None) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    ns = DEFAULT_NS
    if argv:
        ns = tuple(int(a) for a in argv)
    result = run_suite(ns=ns)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
