"""KV shard process entrypoint.

Runs one `KVShardServicer` (an id-hash slice of the embedding tables +
their optimizer slot rows) behind an RPC endpoint. Spawned by the
master's `KVShardGroup` in process mode, or as a dedicated pod on
Kubernetes — the sharded analog of the reference's Redis
embedding-service process (reference:
elasticdl/python/master/embedding_service.py:360-365).

Unlike a PS shard, a KV shard is model-oblivious END TO END (pure
id-keyed row storage; even the sparse optimizer runs master-side), so
it needs no model-spec flags at all.
"""

from __future__ import annotations

import argparse
import signal
import sys
import threading

from elasticdl_tpu.common.args import non_neg_int, pos_int
from elasticdl_tpu.common.log_util import get_logger

logger = get_logger(__name__)


def kv_shard_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="elasticdl_tpu.master.kv_shard_main",
        description="ElasticDL-TPU embedding KV shard",
    )
    p.add_argument("--shard_id", type=non_neg_int, required=True)
    p.add_argument("--num_shards", type=pos_int, required=True)
    p.add_argument("--port", type=non_neg_int, default=0)
    p.add_argument(
        "--port_file", default="",
        help="publish the bound port here (ephemeral-port discovery)",
    )
    p.add_argument("--log_level", default="INFO")
    p.add_argument(
        "--generation", type=non_neg_int, default=0,
        help="fencing epoch of this shard slot (bumped per relaunch; "
        "requests carrying a different epoch are rejected — "
        "rpc/fencing.py)",
    )
    return p


def main(argv=None) -> int:
    args = kv_shard_parser().parse_args(argv)

    import logging
    import os

    logging.getLogger().setLevel(args.log_level.upper())

    # row storage is HOST memory; the chip belongs to the workers. The
    # KV stack (RPC server + embedding store) never imports jax — the
    # env var keeps it that way for anything a handler might pull in.
    os.environ["JAX_PLATFORMS"] = "cpu"

    from elasticdl_tpu.master.kv_shard import KVShardServicer
    from elasticdl_tpu.rpc.server import RpcServer

    servicer = KVShardServicer(
        args.shard_id, args.num_shards, generation=args.generation
    )
    server = RpcServer(servicer.handlers(), port=args.port)
    servicer.attach_admission_stats(server.admission_stats)
    servicer.attach_wire_stats(server.wire)
    servicer.register_metrics()

    from elasticdl_tpu.obs import flight

    flight.install_crash_dump()
    server.start()
    logger.info(
        "KV shard %d/%d (generation %d) listening on :%d",
        args.shard_id,
        args.num_shards,
        args.generation,
        server.port,
    )
    if args.port_file:
        tmp = args.port_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(str(server.port))
        os.replace(tmp, args.port_file)  # atomic: no partial reads

    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda s, f: stop.set())
    signal.signal(signal.SIGINT, lambda s, f: stop.set())
    stop.wait()
    server.stop()
    servicer.close()  # join the mirror drain thread
    return 0


if __name__ == "__main__":
    sys.exit(main())
