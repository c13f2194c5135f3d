"""Aggregator node process entrypoint.

Runs one `AggregatorServicer` (agg/aggregator.py) behind an RPC
endpoint: the host-local combine/forward rung of the aggregation tree.
Spawned by the master's `AggGroup` in process mode — one per worker
host in a real deployment, so the workers' pushes terminate over the
host's local carrier and only the combined deltas cross the host
boundary.

The node is model-oblivious (it sums decoded f32 slices), so unlike
ps_shard_main there is no model-spec flag subset — just the slot
identity, the fencing generation, and the upstream PS endpoints.
"""

from __future__ import annotations

import argparse
import signal
import sys
import threading

from elasticdl_tpu.common.args import non_neg_int
from elasticdl_tpu.common.log_util import get_logger

logger = get_logger(__name__)


def agg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="elasticdl_tpu.agg.agg_main",
        description="ElasticDL-TPU aggregation-tree node",
    )
    p.add_argument("--agg_id", type=non_neg_int, required=True)
    p.add_argument(
        "--ps_endpoints", required=True,
        help="comma-separated upstream PS shard endpoints (index = "
        "shard id)",
    )
    p.add_argument("--port", type=non_neg_int, default=0)
    p.add_argument(
        "--port_file", default="",
        help="publish the bound port here (ephemeral-port discovery)",
    )
    p.add_argument(
        "--generation", type=non_neg_int, default=0,
        help="fencing epoch of this aggregator slot (bumped per "
        "relaunch; requests carrying a different epoch are rejected — "
        "rpc/fencing.py)",
    )
    p.add_argument(
        "--log_level", default="info",
        help="root logger level for this process",
    )
    return p


def main(argv=None) -> int:
    args = agg_parser().parse_args(argv)

    import logging
    import os

    logging.getLogger().setLevel(args.log_level.upper())

    # aggregator math is HOST math (numpy presums) — never initialize
    # or contend for the accelerator (same pin as ps_shard_main)
    os.environ["JAX_PLATFORMS"] = "cpu"

    from elasticdl_tpu.agg.aggregator import AggregatorServicer
    from elasticdl_tpu.rpc.server import RpcServer

    endpoints = [e for e in args.ps_endpoints.split(",") if e]
    servicer = AggregatorServicer(
        args.agg_id,
        endpoints,
        generation=args.generation,
    )
    server = RpcServer(servicer.handlers(), port=args.port)
    servicer.attach_wire_stats(server.wire)
    servicer.attach_admission_stats(server.admission_stats)
    servicer.register_metrics()

    from elasticdl_tpu.obs import flight

    flight.install_crash_dump()
    server.start()
    logger.info(
        "aggregator %d (generation %d) listening on :%d, upstream %s",
        args.agg_id,
        args.generation,
        server.port,
        ",".join(endpoints),
    )
    if args.port_file:
        tmp = args.port_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(str(server.port))
        os.replace(tmp, args.port_file)  # atomic publish

    done = threading.Event()

    def _term(signum, frame):
        logger.info(
            "aggregator %d: signal %d, exiting", args.agg_id, signum
        )
        done.set()

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)
    done.wait()
    servicer.close()
    server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
