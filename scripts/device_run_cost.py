"""Standalone chip probe: what one `worker.device_run` costs its caller
(PERF.md, PR 54). The span is always on, once a call of a training
program: `DeviceRuns.asked` (ONE `memory_stats()` of the worker's
device) and `ready` on the step loop's own thread, or `asked` and
`watch` where the watcher stamps.

    chiprun -- python scripts/device_run_cost.py [--reps N]

With a gigabyte on the device (an empty allocator answers faster than
a worker's), microseconds a call, the median of `--reps`: `asked` +
`ready`, `memory_stats()` alone, and `asked` + `watch` as the step
loop pays them (the watcher's own work is off that thread). One
JSON object on stdout.
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def _each_us(reps, call):
    took = []
    for _ in range(reps):
        t0 = time.perf_counter()
        call()
        took.append((time.perf_counter() - t0) * 1e6)
    return {"median": statistics.median(took), "p99": sorted(took)[int(reps * 0.99)]}


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--reps", type=int, default=2000)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.common.timing import DeviceRuns, PhaseTimers
    from elasticdl_tpu.obs import trace

    device = jax.local_devices()[0]
    held = [jnp.ones((2**26,), jnp.float32) + i for i in range(4)]  # 1 GiB
    loss = jax.block_until_ready(jnp.sum(held[0]))
    runs = DeviceRuns(
        PhaseTimers(sink=trace.record_phase), jax.block_until_ready,
        device.memory_stats,
    )

    def stamped_here():
        runs.ready(runs.asked("jit_window", 16))

    def watched():
        runs.watch(runs.asked("jit_window", 16), loss)

    out = {
        "device": {"platform": device.platform, "kind": device.device_kind},
        "memory_stats": {
            k: v for k, v in (device.memory_stats() or {}).items()
            if k in ("bytes_in_use", "bytes_reserved")
        },
        "reps": args.reps,
        "asked_ready_us": _each_us(args.reps, stamped_here),
        "memory_stats_us": _each_us(args.reps, device.memory_stats),
        "asked_watch_us": _each_us(args.reps, watched),
    }
    runs.close()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
