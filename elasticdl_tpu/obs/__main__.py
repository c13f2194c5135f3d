"""Observability self-check + CI artifact capture.

``python -m elasticdl_tpu.obs --out-dir obs-artifacts`` runs a small
traced probe — a KV shard served over the configured transport tier
(``EDL_TRANSPORT``), a handful of fenced writes/reads plus the
GetTrace/GetMetrics scrape RPCs — then writes three artifacts:

- ``trace.json``    Perfetto-loadable Chrome trace of every probe span
- ``flight.json``   the flight-recorder dump (probe markers included)
- ``metrics.txt``   the Prometheus exposition of the process registry

Exits non-zero when the probe spans are missing (client AND server
sides of the round-trip), so CI catches an instrumentation regression
before a human stares at an empty timeline.

``python -m elasticdl_tpu.obs --spans <run>/logs/worker-0.spans.jsonl
<run>/tb/master.spans.jsonl [--device-trace DIR] --out timeline.json``
is the operator's other use: the processes' phase timelines (the files
each process appends to as it runs) and, optionally, a ``jax.profiler``
trace directory merged into ONE Chrome trace, host phases above device
operations on ``time.time()``'s clock. The device trace's clock starts
where ``start_trace`` was called; the record beside the trace says when
that was (``trace.asked`` in the benchmark probe's ``<pid>.json``
beside ``trace-<pid>/``). A trace that nothing dates is refused.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys


# device events shorter than this stay out of the merged trace: a
# window of ResNet-50 has some 35,000 operations, most under 1 us
MIN_DEVICE_EVENT_SECS = 1e-6


def _asked(trace_dir: str) -> float:
    """Where the device trace's clock starts, from the record beside it."""
    trace_dir = trace_dir.rstrip("/")
    match = re.fullmatch(r"trace-(\d+)", os.path.basename(trace_dir))
    record = match and os.path.join(
        os.path.dirname(trace_dir), f"{match.group(1)}.json"
    )
    if record and os.path.isfile(record):
        with open(record) as f:
            asked = (json.load(f).get("trace") or {}).get("asked")
        if asked is not None:
            return float(asked)
    raise SystemExit(
        f"obs: no record beside {trace_dir} (<pid>.json with trace.asked "
        "next to trace-<pid>/) says where its clock starts: refused"
    )


def merge_timeline(span_files, device_trace, out) -> int:
    """Span files (+ one device trace) -> one Chrome trace at `out`."""
    from elasticdl_tpu.obs import trace

    spans = []
    for path in span_files:
        label = os.path.basename(path).split(".spans.")[0]
        for s in trace.load_span_file(path):
            s["process"] = f"{label} (pid {s.get('pid')})"
            spans.append(s)
    host = len(spans)
    if device_trace:
        files = sorted(glob.glob(os.path.join(
            device_trace, "plugins", "profile", "*", "*.xplane.pb"
        )))
        if not files:
            raise SystemExit(f"obs: no .xplane.pb under {device_trace}")
        spans += trace.spans_from_device_trace(
            files[-1], _asked(device_trace), min_dur=MIN_DEVICE_EVENT_SECS
        )
    with open(out, "w") as f:
        json.dump(trace.chrome_trace_from_spans(spans), f)
    print(f"obs[timeline]: {host} host spans from {len(span_files)} file(s), "
          f"{len(spans) - host} device events -> {out}")
    return 0 if host else 1


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m elasticdl_tpu.obs", description=__doc__
    )
    parser.add_argument(
        "--out-dir",
        default="obs-artifacts",
        help="directory receiving trace.json / flight.json / metrics.txt",
    )
    parser.add_argument(
        "--rounds", type=int, default=8, help="probe RPC round-trips"
    )
    parser.add_argument(
        "--spans", nargs="+", metavar="FILE",
        help="merge these .spans.jsonl files into one Chrome trace "
        "instead of running the self-check",
    )
    parser.add_argument(
        "--device-trace", metavar="DIR",
        help="with --spans: a jax.profiler trace directory to lay under "
        "the host phases",
    )
    parser.add_argument(
        "--out", default="timeline.json", help="with --spans: the output"
    )
    args = parser.parse_args(argv)
    if args.spans:
        return merge_timeline(args.spans, args.device_trace, args.out)

    from elasticdl_tpu.common.constants import ENV_TRACE_SAMPLE
    from elasticdl_tpu.master.kv_shard import KVShardServicer
    from elasticdl_tpu.obs import fetch, flight, metrics, trace
    from elasticdl_tpu.rpc.client import RpcClient
    from elasticdl_tpu.rpc.server import RpcServer

    os.environ[ENV_TRACE_SAMPLE] = "1"
    trace.refresh()

    os.makedirs(args.out_dir, exist_ok=True)
    flight.record("obs_selfcheck_begin", rounds=args.rounds)

    servicer = KVShardServicer(shard_id=0, num_shards=1)
    servicer.register_metrics()
    server = RpcServer(servicer.handlers(), port=0)
    server.start()
    client = RpcClient(f"localhost:{server.port}")
    try:
        with trace.span("obs.selfcheck", cat="probe", root=True):
            for i in range(args.rounds):
                # probe shard is freshly built at generation 0; the
                # epoch stamp keeps the calls on the fenced contract
                client.call(
                    "KVUpdate",
                    {"epoch": 0, "layer": "probe", "ids": [i],
                     "values": [[float(i)]]},
                    timeout=30,
                )
                client.call(
                    "KVLookup",
                    {"epoch": 0, "layer": "probe", "ids": [i]},
                    timeout=30,
                )
        transport = (
            client._transport.name if client._transport else "grpc"
        )
        flight.record("obs_selfcheck_probe_done", transport=transport)
        trace_path = os.path.join(args.out_dir, "trace.json")
        fetch.fetch_chrome_trace([client], path=trace_path)
    finally:
        client.close()
        server.stop()

    flight_path = flight.RECORDER.dump(
        os.path.join(args.out_dir, "flight.json")
    )
    metrics_path = os.path.join(args.out_dir, "metrics.txt")
    with open(metrics_path, "w") as f:
        f.write(metrics.get_registry().prometheus_text())

    spans = trace.RECORDER.snapshot()
    names = {s["name"] for s in spans}
    missing = {
        "rpc.client.KVUpdate",
        "rpc.server.KVUpdate",
        "rpc.client.KVLookup",
        "rpc.server.KVLookup",
        "obs.selfcheck",
    } - names
    print(f"obs[selfcheck]: transport={transport} spans={len(spans)}")
    print(f"obs[selfcheck]: wrote {trace_path}")
    print(f"obs[selfcheck]: wrote {flight_path}")
    print(f"obs[selfcheck]: wrote {metrics_path}")
    if missing:
        print(
            f"obs[selfcheck]: FAILED — probe spans missing: "
            f"{sorted(missing)}",
            file=sys.stderr,
        )
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
