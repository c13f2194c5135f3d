"""ResNet-50 — the north-star benchmark (BASELINE.md: "ResNet-50
images/sec/chip").

Reference model: model_zoo/resnet50_subclass/resnet50_subclass.py:1-221
(rebuilt TPU-first in elasticdl_tpu/models/resnet50_subclass.py). The
reference never published a ResNet number; BASELINE.md's north star is
throughput per chip, so this bench measures it TWO ways and prints ONE
JSON line:

1. **chip** (headline, images/sec/chip): the full train step — fwd,
   bwd, SGD-momentum + weight decay, BN stat update — scanned K steps
   back-to-back with DEVICE-RESIDENT data, bf16 compute / f32 params.
   MFU comes from XLA's own cost analysis of the compiled step (scan
   body counted once; multiplied by the trip count) against the chip's
   published peak (bench.PEAK_BF16_TFLOPS).

2. **runtime** (elastic number): the same model trained end-to-end
   through the elastic PS runtime — real gRPC master, RecordIO shards,
   window mode with chained delta syncs, BN aux riding the sync, bf16
   transport — at 64x64 input, convergence-gated.

Physics of the gap: ResNet-50 consumes ~80 KFLOP per uint8 input
byte, so feeding a v5e's 197 bf16 TFLOP/s needs ~2.5 GB/s of input; the
JSON carries the measured h2d bandwidth. Where the gap between the two
numbers goes on a local chip is not measured on this machine.
"""

import json
import os
import statistics
import sys
import tempfile
import time


def measure_link_bandwidth(nbytes=32 * 1024 * 1024, reps=3):
    """Sustained h2d bandwidth of the host<->device link (MB/s)."""
    import jax
    import numpy as np

    buf = np.random.default_rng(0).integers(
        0, 255, size=nbytes, dtype=np.uint8
    )
    best = 0.0
    for _ in range(reps):
        t0 = time.time()
        jax.device_put(buf).block_until_ready()
        best = max(best, nbytes / (time.time() - t0))
    return best / 1e6


def chip_throughput(res=224, batch=64, steps=16, reps=4, num_classes=1000):
    """Device-resident scanned train steps -> (imgs/sec, TFLOP/s, mfu,
    loss0); mfu is None off the TPU (not a device metric there)."""
    import jax
    import jax.numpy as jnp
    import optax
    from bench import mfu_of
    from jax import lax

    from elasticdl_tpu.common.device import device_report
    from elasticdl_tpu.models import resnet50_subclass as m

    model = m.custom_model(num_classes=num_classes, bfloat16=True)
    rng = jax.random.PRNGKey(0)
    images = jax.random.randint(
        rng, (batch, res, res, 3), 0, 255, dtype=jnp.int32
    ).astype(jnp.uint8)
    labels = jax.random.randint(rng, (batch,), 0, num_classes, jnp.int32)
    variables = model.init(rng, images, train=True)
    params, aux = variables["params"], variables["batch_stats"]
    tx = m.optimizer()
    opt_state = tx.init(params)

    def one_step(carry, _):
        params, aux, opt_state = carry

        def loss_fn(p):
            out, new_vars = model.apply(
                {"params": p, "batch_stats": aux},
                images,
                train=True,
                mutable=["batch_stats"],
            )
            return m.loss(out, labels), new_vars["batch_stats"]

        (l, new_aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params
        )
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), new_aux, opt_state), l

    def k_steps(params, aux, opt_state):
        return lax.scan(one_step, (params, aux, opt_state), None, length=steps)

    # donated params/opt buffers: no f32 copy of the master weights
    # between scans
    lowered = jax.jit(k_steps, donate_argnums=(0, 2)).lower(
        params, aux, opt_state
    )
    compiled = lowered.compile()
    # XLA counts the scan body ONCE regardless of trip count
    body_flops = compiled.cost_analysis()["flops"]
    state = (params, aux, opt_state)
    state, losses = compiled(*state)  # warm-up execution
    jax.block_until_ready(state)
    loss0 = float(losses[0])
    best = 0.0
    for _ in range(reps):
        t0 = time.time()
        state, losses = compiled(*state)
        jax.block_until_ready(losses)
        dt = time.time() - t0
        best = max(best, steps * batch / dt)
    tflops = body_flops * (best / batch) / 1e12  # flops/step * steps/sec
    return best, tflops, mfu_of(tflops, device_report()), loss0


def runtime_throughput(window=32, minibatch=128, n_records=32768):
    """ResNet-50 through the elastic PS runtime (window mode, bf16
    transport, BN aux riding the sync) on synthetic 64x64 RecordIO."""
    from bench import mfu_of, run_job

    from elasticdl_tpu.common.device import device_report
    from elasticdl_tpu.models import resnet50_subclass as model_module
    from elasticdl_tpu.models.record_codec import (
        write_synthetic_image_records,
    )

    tmp = tempfile.mkdtemp(prefix="edl_bench_resnet_")
    path = os.path.join(tmp, "imgs.rio")
    write_synthetic_image_records(
        path, n_records, model_module.IMAGE_SHAPE, model_module.NUM_CLASSES
    )
    os.environ["EDL_BENCH_MFU"] = "1"
    imgs_per_sec, worker, elapsed = run_job(
        model_module,
        path,
        n_records,
        minibatch=minibatch,
        records_per_task=window * minibatch,
        epochs=1,
        local_updates=window,
        grads_to_wait=1,
        transport_dtype="bfloat16",
        spec_overrides={"model": model_module.custom_model(bfloat16=True)},
    )
    losses = worker.task_losses
    tail = statistics.median(losses[-3:]) if losses else None
    per_image = worker.window_flops / (window * minibatch)
    mfu = mfu_of(per_image * imgs_per_sec / 1e12, device_report())
    print(
        f"bench_resnet[runtime]: {n_records} imgs in {elapsed:.1f}s = "
        f"{imgs_per_sec:.1f} img/s; tail loss {tail}; "
        f"phases {worker.timers.summary()}",
        file=sys.stderr,
    )
    return imgs_per_sec, mfu, tail


def main():
    from elasticdl_tpu.common.args import enable_compile_cache
    from elasticdl_tpu.common.device import require_device

    # the worker's rule: the CPU only when JAX_PLATFORMS=cpu asked for it
    on_tpu = require_device("bench_resnet")["platform"] == "tpu"
    enable_compile_cache()

    link_mbps = measure_link_bandwidth() if on_tpu else None
    if on_tpu:
        # b256: batch is the biggest MFU lever
        res, batch, steps = 224, 256, 8
    else:  # CPU smoke: tiny everything
        res, batch, steps = 64, 8, 2
    chip_ips, chip_tflops, chip_mfu, chip_loss = chip_throughput(
        res=res, batch=batch, steps=steps, reps=4 if on_tpu else 1
    )
    print(
        f"bench_resnet[chip]: {res}x{res} b{batch}: {chip_ips:.1f} img/s = "
        f"{chip_tflops:.1f} TFLOP/s"
        + (f" = {100 * chip_mfu:.1f}% MFU" if on_tpu else "")
        + f"; first loss {chip_loss:.2f}",
        file=sys.stderr,
    )
    chip64_ips = chip64_mfu = None
    if on_tpu:
        chip64_ips, _t, chip64_mfu, _l = chip_throughput(
            res=64, batch=256, steps=32, reps=4, num_classes=10
        )
        print(
            f"bench_resnet[chip64]: {chip64_ips:.1f} img/s = "
            f"{100 * chip64_mfu:.1f}% MFU",
            file=sys.stderr,
        )

    rt_ips, rt_mfu, rt_tail = runtime_throughput(
        window=32 if on_tpu else 2,
        # 8 whole-window tasks: with only 4, end-of-job wait_poll and
        # the final sync tail were ~30% of the measured window
        minibatch=128 if on_tpu else 16,
        n_records=32768 if on_tpu else 64,
    )
    if on_tpu and rt_tail is not None:
        assert rt_tail < 2.0, f"runtime run diverged: tail {rt_tail:.3f}"

    print(
        json.dumps(
            {
                "metric": "resnet50_images_per_sec_chip",
                "value": round(chip_ips, 1),
                "unit": "images/sec/chip",
                "resolution": res,
                "chip_tflops_per_sec": round(chip_tflops, 2),
                "chip_mfu_vs_v5e_bf16_peak": (
                    round(chip_mfu, 4) if on_tpu else None
                ),
                "chip_64px_images_per_sec": (
                    round(chip64_ips, 1) if chip64_ips else None
                ),
                "chip_64px_mfu": (
                    round(chip64_mfu, 4) if chip64_mfu else None
                ),
                "runtime_images_per_sec_64px": round(rt_ips, 1),
                "runtime_mfu": round(rt_mfu, 4) if rt_mfu else None,
                "runtime_tail_loss": (
                    round(rt_tail, 4) if rt_tail is not None else None
                ),
                "link_bandwidth_MBps": (
                    round(link_mbps, 1) if link_mbps else None
                ),
                "protocol": (
                    "chip = full train step (fwd+bwd+SGD-momentum+WD+BN "
                    "update), bf16 compute/f32 params, device-resident "
                    "data, K-step lax.scan, best of 4 timed reps after "
                    "an untimed compile+warm-up; MFU from XLA "
                    "cost_analysis of the scan body x trip count over "
                    "the chip's published bf16 peak (null off the "
                    "TPU: not measured). runtime = the same model end-to-end "
                    "through the elastic PS runtime (gRPC master, "
                    "RecordIO, 32-step windows, chained syncs, bf16 "
                    "wire), convergence-gated; link_bandwidth_MBps is "
                    "the measured h2d bandwidth (ResNet needs ~2.5 "
                    "GB/s to saturate a v5e)"
                ),
            }
        )
    )


if __name__ == "__main__":
    main()
