"""PS shard process entrypoint.

Runs one `PSShardServicer` (a contiguous slice of the flat model
vector + its optimizer state) behind an RPC endpoint. Spawned by the
master's `PSShardGroup` in process mode, or as a dedicated "ps" pod
on Kubernetes (cluster/k8s_backend.build_ps_pod_manifest) — the
sharded analog of the reference's Redis embedding-service process
(reference: elasticdl/python/master/embedding_service.py:360-365,
`python -m ...embedding_service` inside the pod).

The shard only needs the user's OPTIMIZER (slice math is
model-oblivious), so it takes the model-spec flag subset and resolves
`optimizer()` from the model zoo the same way master and workers do —
the flag namespace stays the inter-process config protocol.
"""

from __future__ import annotations

import argparse
import signal
import sys
import threading

from elasticdl_tpu.common.args import (
    add_model_spec_args,
    non_neg_int,
    pos_int,
)
from elasticdl_tpu.common.log_util import get_logger

logger = get_logger(__name__)


def ps_shard_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="elasticdl_tpu.master.ps_shard_main",
        description="ElasticDL-TPU parameter-server shard",
    )
    add_model_spec_args(p)
    p.add_argument("--shard_id", type=non_neg_int, required=True)
    p.add_argument("--num_shards", type=pos_int, required=True)
    p.add_argument("--port", type=non_neg_int, default=0)
    p.add_argument(
        "--port_file", default="",
        help="publish the bound port here (ephemeral-port discovery)",
    )
    p.add_argument("--grads_to_wait", type=pos_int, default=1)
    p.add_argument("--use_async", action="store_true")
    p.add_argument("--lr_staleness_modulation", action="store_true")
    p.add_argument("--staleness_window", type=non_neg_int, default=0)
    p.add_argument(
        "--generation", type=non_neg_int, default=0,
        help="fencing epoch of this shard slot (bumped per relaunch; "
        "requests carrying a different epoch are rejected — "
        "rpc/fencing.py)",
    )
    p.add_argument(
        "--dedup_cap", type=non_neg_int, default=0,
        help="push dedup ring capacity (0 = servicer default; the "
        "group sizes it as num_workers x max in-flight syncs)",
    )
    p.add_argument(
        "--fanin_combine", action="store_true",
        help="hierarchical fan-in: combine compatible concurrent "
        "pushes outside the shard lock (master/fanin.py; default "
        "honors EDL_FANIN_COMBINE)",
    )
    return p


def main(argv=None) -> int:
    args = ps_shard_parser().parse_args(argv)

    import logging
    import os

    logging.getLogger().setLevel(args.log_level.upper())

    # PS slice math is HOST math; the chip belongs to the workers. The
    # env var covers anything this process spawns, the config pin this
    # process itself.
    os.environ["JAX_PLATFORMS"] = "cpu"
    from elasticdl_tpu.common.device import pin_cpu

    pin_cpu()

    from elasticdl_tpu.api.model_spec import get_model_spec
    from elasticdl_tpu.master.ps_optimizer import PSOptimizer
    from elasticdl_tpu.master.ps_shard import PSShardServicer
    from elasticdl_tpu.rpc.server import RpcServer

    spec = get_model_spec(
        model_zoo=args.model_zoo,
        model_def=args.model_def,
        model_params=args.model_params,
        dataset_fn=args.dataset_fn,
        loss=args.loss,
        optimizer=args.optimizer,
        eval_metrics_fn=args.eval_metrics_fn,
        prediction_outputs_processor=args.prediction_outputs_processor,
    )
    servicer = PSShardServicer(
        args.shard_id,
        args.num_shards,
        optimizer=PSOptimizer(spec.optimizer()),
        grads_to_wait=args.grads_to_wait,
        use_async=args.use_async,
        lr_staleness_modulation=args.lr_staleness_modulation,
        staleness_window=args.staleness_window,
        generation=args.generation,
        dedup_cap=args.dedup_cap or None,
        # flag forces combining on; absent flag defers to the env knob
        fanin_combine=True if args.fanin_combine else None,
    )
    server = RpcServer(servicer.handlers(), port=args.port)
    servicer.attach_wire_stats(server.wire)
    servicer.attach_admission_stats(server.admission_stats)
    servicer.register_metrics()

    from elasticdl_tpu.obs import flight

    flight.install_crash_dump()
    server.start()
    logger.info(
        "PS shard %d/%d (generation %d) listening on :%d",
        args.shard_id,
        args.num_shards,
        args.generation,
        server.port,
    )
    if args.port_file:
        tmp = args.port_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(str(server.port))
        import os

        os.replace(tmp, args.port_file)  # atomic publish

    done = threading.Event()

    def _term(signum, frame):
        logger.info("PS shard %d: signal %d, exiting", args.shard_id, signum)
        done.set()

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)
    done.wait()
    server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
