"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py          # on a machine with a TPU; fails without one
    python chip_smoke.py --cpu    # the sandbox's plumbing check, cut down

Drives the main path once through the entry points a user calls — two
`python -m elasticdl_tpu.master.main --worker_backend process` jobs on
the zoo's ResNet-50 (bf16 compute, 64x64 synthetic RecordIO written
from a seed, 10 classes):

- window job: `--local_updates W`, 8 whole-window tasks for each chip,
  one worker process per chip. After the third task completes one
  worker is SIGKILLed; the WorkerManager relaunches it and the
  replacement takes the freed chip. With several workers a chaos
  latency plan (rpc/chaos.py, armed only from the kill until the
  replacement's first task) slows the survivors' GetTask so the
  replacement finds work whatever the chip's speed;
- per-step job: `--local_updates 0 --grads_to_wait 1`, one worker —
  the mode in which the master's own PSOptimizer runs.

Each job must exit 0, finish at exactly records/minibatch versions,
log a finite loss for every task, and write an --output that reads
back finite; every worker's boot line must name the expected platform.
Then a kernel phase compiles forward and both backward flash-attention
kernels and compares them with the f32 reference, and on a host with
several chips the window job runs again with ONE worker over all of
them and one transformer train step runs on each four-chip mesh.

This process never imports jax: a chip belongs to one process at a
time, so the probe, the jobs and the kernel check are children that
run one after another. Stdout is two JSON lines: the per-phase record,
then — last — exactly `{"ok": true, "device": {"platform", "kind",
"count"}}` with the device as jax reports it. Any failed phase exits
non-zero and prints neither.
"""

import argparse
import datetime
import json
import math
import os
import re
import shutil
import signal
import socket
import statistics
import subprocess
import sys
import tempfile
import time

import grpc

from elasticdl_tpu.common.args import (
    compile_cache_dir,
    resolve_compile_cache_envs,
)
from elasticdl_tpu.common.constants import (
    ENV_CHAOS_SPEC,
    ENV_TB_BACKEND,
    ENV_WORKER_LOG_DIR,
)
from elasticdl_tpu.common.device import package_env, probe_device

ROOT = os.path.dirname(os.path.abspath(__file__))
DEADLINE_SECS = 1150  # the contract allows 1200, compilation included
T0 = time.monotonic()


def say(msg):
    print(f"chip_smoke[{time.monotonic() - T0:6.1f}s] {msg}", file=sys.stderr)


def fail(msg):
    raise SystemExit(f"chip_smoke FAILED: {msg}")


def remaining():
    left = DEADLINE_SECS - (time.monotonic() - T0)
    if left <= 0:
        fail("out of time")
    return left


def cache_entries():
    path = compile_cache_dir()
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def child_env(extra=None):
    return package_env({**os.environ, **resolve_compile_cache_envs(), **(extra or {})})


# ---------------------------------------------------------------- the jobs

_STAMP = r"(\d{4}-\d\d-\d\d \d\d:\d\d:\d\d,\d{3}) "
_BOOT = re.compile(
    r"Worker \d+ boot: platform=(\S+) device_kind=(.+) chips=\[(.*)\]"
)
_DONE = re.compile(_STAMP + r".*Worker \d+ task \d+ done \(last loss ")
_STARTED = re.compile(_STAMP + r".*Started worker (\d+) \(pid (\d+)\)")


def _when(stamp):
    return datetime.datetime.strptime(stamp, "%Y-%m-%d %H:%M:%S,%f").timestamp()


def _read(path):
    if not os.path.exists(path):
        return ""
    with open(path, errors="replace") as f:
        return f.read()


def worker_logs(log_dir):
    """{worker_id: {"boot": {"platform", "device_kind", "chips"} | None,
    "done": [time of each completed task]}} parsed from the worker logs."""
    out = {}
    for name in sorted(os.listdir(log_dir)) if os.path.isdir(log_dir) else ():
        named = re.fullmatch(r"worker-(\d+)\.log", name)
        if named is None:  # a worker's span file lies beside its log
            continue
        text = _read(os.path.join(log_dir, name))
        wid = int(named.group(1))
        boot = _BOOT.search(text)
        out[wid] = {
            "boot": boot and {
                "platform": boot.group(1),
                "device_kind": boot.group(2),
                "chips": [int(c) for c in boot.group(3).split(",") if c.strip()],
            },
            "done": [_when(m.group(1)) for m in _DONE.finditer(text)],
        }
    return out


def worker_pids(master_pid):
    """{worker_id: pid} of the live worker children of the master, from
    /proc."""
    pids = {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
            with open(f"/proc/{entry}/cmdline", "rb") as f:
                argv = f.read().decode(errors="replace").split("\0")
        except (OSError, IndexError, ValueError):
            continue  # the process exited while we looked
        if ppid == master_pid and "elasticdl_tpu.worker.main" in argv:
            pids[int(argv[argv.index("--worker_id") + 1])] = int(entry)
    return pids


def holds_tpu_runtime(pid):
    """libtpu is mapped into a process when (and only when) it
    initialises the TPU backend."""
    return "libtpu" in _read(f"/proc/{pid}/maps")


def all_finite(tree):
    import numpy as np

    if isinstance(tree, dict):
        return all(all_finite(v) for v in tree.values())
    if isinstance(tree, (list, tuple)):
        return all(all_finite(v) for v in tree)
    return tree is None or bool(np.all(np.isfinite(np.asarray(tree, np.float32))))


def run_job(name, run_dir, platform, *, workers, window, minibatch, records,
            per_task, kill):
    """One master.main job as a child; returns the phase record."""
    from elasticdl_tpu.master.checkpoint import load_model_file
    from elasticdl_tpu.models import resnet50_subclass as zoo
    from elasticdl_tpu.models.record_codec import write_synthetic_image_records
    from elasticdl_tpu.rpc.client import RpcClient

    job_dir = os.path.join(run_dir, name)
    data_dir = os.path.join(job_dir, "data")
    log_dir = os.path.join(job_dir, "logs")
    output = os.path.join(job_dir, "final.model")
    latch = os.path.join(job_dir, "slow-survivors.armed")
    os.makedirs(data_dir)
    say(f"{name}: writing {records} records")
    write_synthetic_image_records(
        os.path.join(data_dir, "imgs.rio"), records, zoo.IMAGE_SHAPE,
        zoo.NUM_CLASSES, seed=0,
    )
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    # the master's own metrics sink, as JSONL: one train/loss per
    # applied update (a window, or a step of the per-step job)
    extra = {ENV_WORKER_LOG_DIR: log_dir, ENV_TB_BACKEND: "jsonl"}
    if kill and workers > 1:
        # only the incumbents (ids 0..workers-1) are slowed, and only
        # while the latch exists: the replacement has a fresh id
        extra[ENV_CHAOS_SPEC] = json.dumps({"faults": [{
            "kind": "latency", "methods": ["GetTask"], "roles": ["worker"],
            "targets": [str(w) for w in range(workers)], "side": "client",
            "latency_ms": 10000, "armed_file": latch,
        }]})
    argv = [
        sys.executable, "-m", "elasticdl_tpu.master.main",
        "--model_zoo", os.path.dirname(zoo.__file__),
        "--model_def", "resnet50_subclass.custom_model",
        "--model_params", "bfloat16=True",
        "--minibatch_size", str(minibatch),
        "--training_data_dir", data_dir,
        "--records_per_task", str(per_task),
        "--num_epochs", "1",
        "--grads_to_wait", "1",
        "--local_updates", str(window),
        "--num_workers", str(workers),
        "--worker_backend", "process",
        "--port", str(port),
        "--tensorboard_log_dir", os.path.join(job_dir, "tb"),
        "--output", output,
    ]
    entries_before = cache_entries()
    master_log = os.path.join(job_dir, "master.log")
    killed = None  # (worker_id, chips) once the preemption happened
    devices = {}  # worker_id -> the device field beside its phase stats
    with open(master_log, "wb") as logf:
        master = subprocess.Popen(
            argv, env=child_env(extra), stdout=logf, stderr=logf,
            start_new_session=True,
        )
    say(f"{name}: master pid {master.pid}, {workers} worker(s)")
    client = RpcClient(f"localhost:{port}")
    try:
        while master.poll() is None:
            remaining()
            time.sleep(0.1)
            if holds_tpu_runtime(master.pid):
                fail(f"{name}: the master initialised a TPU backend")
            try:
                stats = client.call("GetSchedStats", {}, timeout=5.0)
            except grpc.RpcError:
                continue  # the master is still booting, or shutting down
            devices.update(
                {int(w): d for w, d in stats["phases"]["devices"].items()}
            )
            done = stats["goodput"]["completed_records"] // per_task
            logs = worker_logs(log_dir) if kill else {}
            # the victim has already completed a task (its log line can
            # trail the master's count by a moment), so every incumbent
            # is seen to train before one is taken away
            trained = [w for w, l in logs.items() if l["done"]]
            if kill and killed is None and done >= 3 and trained:
                victim = min(trained)
                pid = worker_pids(master.pid)[victim]
                if workers > 1:
                    open(latch, "w").close()
                os.kill(pid, signal.SIGKILL)
                killed = (victim, logs[victim]["boot"]["chips"])
                say(f"{name}: {done} tasks done; SIGKILLed worker {victim} "
                    f"(pid {pid}, chips {killed[1]})")
            if os.path.exists(latch) and any(w >= workers for w in trained):
                os.unlink(latch)
                say(f"{name}: the replacement trained; survivors released")
        rc = master.returncode
    finally:
        client.close()
        if master.poll() is None:
            os.killpg(master.pid, signal.SIGKILL)
            master.wait()
    if rc != 0:
        sys.stderr.write(_read(master_log)[-6000:])
        for wid in sorted(worker_logs(log_dir)):
            sys.stderr.write(
                _read(os.path.join(log_dir, f"worker-{wid}.log"))[-3000:]
            )
        fail(f"{name}: master exited {rc}")

    # -- what came out ------------------------------------------------------
    model = load_model_file(output)
    want = records // minibatch
    if model.version != want:
        fail(f"{name}: finished at version {model.version}, want {want}")
    if not (all_finite(model.params) and all_finite(model.aux)):
        fail(f"{name}: the saved model is not finite")
    logs = worker_logs(log_dir)
    started = {
        int(m.group(2)): _when(m.group(1))
        for m in _STARTED.finditer(_read(master_log))
    }
    with open(os.path.join(job_dir, "tb", "events.jsonl")) as f:
        events = [json.loads(line) for line in f]
    losses = [
        e["value"]
        for e in sorted(events, key=lambda e: e["step"])
        if e["tag"] == "train/loss"
    ]
    updates = records // (minibatch * max(window, 1))
    if len(losses) != updates or not all(map(math.isfinite, losses)):
        fail(f"{name}: want a finite loss for each of {updates} applied "
             f"updates, the master recorded {losses}")
    for wid, l in logs.items():
        if not l["boot"] or l["boot"]["platform"] != platform:
            fail(f"{name}: worker {wid} boot line {l['boot']} does not "
                 f"name platform {platform!r}")
        reported = devices.get(wid)
        if reported and reported["platform"] != platform:
            fail(f"{name}: worker {wid} reported device {reported}")
    if not any(d["platform"] == platform for d in devices.values()):
        fail(f"{name}: no worker reported its device beside its phase stats")
    incumbents = [logs[w]["boot"]["chips"] for w in range(workers)]
    if len({c for chips in incumbents for c in chips}) != sum(map(len, incumbents)):
        fail(f"{name}: live workers shared a chip: {incumbents}")
    idle = [w for w in range(workers) if not logs[w]["done"]]
    if idle:
        fail(f"{name}: workers {idle} completed no task")
    setup = {"first_worker": round(logs[0]["done"][0] - started[0], 1)}
    if kill:
        if killed is None:
            fail(f"{name}: the job ended before a worker could be killed")
        new = max(logs)  # fresh ids: the newest worker is the replacement
        if new < workers or not logs[new]["done"]:
            fail(f"{name}: want a replacement that trained, got tasks done "
                 f"by worker { {w: len(l['done']) for w, l in logs.items()} }")
        if logs[new]["boot"]["chips"] != killed[1]:
            fail(f"{name}: replacement holds chips {logs[new]['boot']['chips']}"
                 f", its predecessor released {killed[1]}")
        setup["replacement"] = round(logs[new]["done"][0] - started[new], 1)
    if window and platform == "tpu":
        # training works: the tail of the loss sits below its start.
        # (Not asked of --cpu: 16 steps at minibatch 8 are noise.)
        if not statistics.median(losses[-2:]) < losses[0]:
            fail(f"{name}: loss did not fall: {losses}")
    record = {
        "exit_code": rc,
        "workers": workers,
        "versions": model.version,
        "applied_updates": updates,
        "first_loss": losses[0],
        "last_loss": losses[-1],
        "chips_by_worker": {str(w): l["boot"]["chips"] for w, l in logs.items()},
        "killed_worker": killed[0] if killed else None,
        "relaunches": len(logs) - workers,
        # set-up time from one run (process spawn to the first completed
        # window, compile included), not a metric
        "setup_seconds_spawn_to_first_window": setup,
        "master_held_tpu": False,
        "cache_entries": [entries_before, cache_entries()],
    }
    say(f"{name}: ok {json.dumps(record)}")
    return record


# ------------------------------------------------------- children with jax


def run_child(name, cpu):
    """`chip_smoke.py --child NAME` as a child that owns the chip;
    returns the JSON object on its last stdout line."""
    entries_before = cache_entries()
    argv = [sys.executable, os.path.abspath(__file__), "--child", name]
    out = subprocess.run(
        argv + (["--cpu"] if cpu else []), env=child_env(),
        stdout=subprocess.PIPE, text=True, timeout=remaining(),
    )
    if out.returncode != 0:
        fail(f"{name}: child exited {out.returncode}")
    record = json.loads(out.stdout.strip().splitlines()[-1])
    record["exit_code"] = out.returncode
    record["cache_entries"] = [entries_before, cache_entries()]
    say(f"{name}: ok {json.dumps(record)}")
    return record


def child_kernels(cpu):
    """value_and_grad through flash_attention — forward, dq and dk/dv
    kernels — against reference_attention in f32, at the head shapes
    a transformer's configs produce."""
    from elasticdl_tpu.common.args import enable_compile_cache
    from elasticdl_tpu.common.device import require_device
    from elasticdl_tpu.ops.flash_attention import (
        REFERENCE_TOLERANCE,
        check_against_reference,
    )

    device = require_device("chip_smoke kernels")
    enable_compile_cache()
    shapes = (
        [(1, 256, 2, 64), (1, 256, 2, 128)]
        if cpu
        else [(1, 2048, 8, 64), (1, 8192, 8, 64), (1, 2048, 8, 128)]
    )
    # latent attention as the routed cell calls it: values of 128 under
    # keys of 192 and deepseek-v2-lite's `mla_softmax_scale`
    latent = {"v_width": 128, "scale": 0.11472138679292611}
    # fewer key-value heads than query heads, read where they lie: a
    # group of 8 in place under a band, a group of 4 folded
    cases = [(shape, {}) for shape in shapes] + [
        ((1, 256, 2, 192) if cpu else (4, 2048, 16, 192), latent),
        ((1, 256, 4, 128) if cpu else (1, 2048, 16, 128),
         {"kv_heads": 2, "window": 128 if cpu else 512}),
        ((1, 256, 4, 64) if cpu else (2, 2048, 8, 64), {"kv_heads": 2}),
    ]
    errors = {}
    for shape, how in cases:
        name = "x".join(map(str, shape)) + "".join(
            f"-{key}{value:.4g}" for key, value in how.items()
        )
        errors[name] = check_against_reference(shape, interpret=cpu, **how)
    print(json.dumps({
        "platform": device["platform"], "interpret": cpu,
        "tolerance": REFERENCE_TOLERANCE, "max_error_over_max_ref": errors,
    }))
    worst = max(e for per in errors.values() for e in per.values())
    if not worst <= REFERENCE_TOLERANCE:
        raise SystemExit(f"kernel error {worst} above {REFERENCE_TOLERANCE}")


def child_meshes(cpu):
    """One transformer_lm.build_train_step step on each of the two
    four-device meshes __graft_entry__.dryrun_multichip builds — (pp 2,
    sp 2) and (sp 2, tp 2)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from __graft_entry__ import dryrun_meshes
    from elasticdl_tpu.common.args import enable_compile_cache
    from elasticdl_tpu.common.device import require_device
    from elasticdl_tpu.models.transformer_lm import (
        TransformerConfig,
        build_train_step,
        init_params,
        place_params,
    )

    device = require_device("chip_smoke meshes")
    enable_compile_cache()
    cfg = TransformerConfig(
        vocab=8192, d_model=512, n_heads=8, d_ff=2048, n_layers=8,
        n_experts=0, dtype=jnp.float32 if cpu else jnp.bfloat16,
    )
    batch, seq = (4, 64) if cpu else (8, 1024)
    losses = {}
    for mesh in dryrun_meshes(jax.devices()[:4]):
        rng = np.random.default_rng(0)
        params = place_params(init_params(rng, cfg), cfg, mesh)
        opt = optax.adam(1e-3)
        step = build_train_step(cfg, mesh, opt)
        tokens = jnp.asarray(
            rng.integers(0, cfg.vocab, size=(batch, seq + 1)), dtype=jnp.int32
        )
        _p, _o, loss = step(params, opt.init(params), tokens)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        name = ",".join(f"{a}{n}" for a, n in sizes.items() if n > 1)
        losses[name] = float(loss)
    print(json.dumps({
        "platform": device["platform"], "chips": device["chips"],
        "loss_by_mesh": losses,
    }))
    if not all(math.isfinite(l) for l in losses.values()):
        raise SystemExit(f"non-finite loss: {losses}")


# -------------------------------------------------------------------- main


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--cpu", action="store_true",
        help="cut-down plumbing check on the CPU (kernels interpreted)",
    )
    parser.add_argument("--child", choices=["kernels", "meshes"],
                        help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.child:
        return {"kernels": child_kernels, "meshes": child_meshes}[args.child](
            args.cpu
        )
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # every child inherits it
    device = probe_device()
    # --cpu ignores the CPU device count: under XLA_FLAGS it is virtual
    chips = 1 if args.cpu else len(device["chips"])
    if device["platform"] != ("cpu" if args.cpu else "tpu"):
        fail(f"jax found {device} — this needs a TPU (or --cpu for the "
             "sandbox's plumbing check)")
    say(f"device {device}; compile cache {compile_cache_dir()}")

    from elasticdl_tpu.data.recordio import _load_native as recordio_native
    from elasticdl_tpu.master.embedding_store import _load_native as kv_native

    minibatch, window = (8, 2) if args.cpu else (128, 8)
    per_window_task = window * minibatch
    run_dir = tempfile.mkdtemp(prefix="edl_chip_smoke_")
    phases = {}
    try:
        phases["window_job"] = run_job(
            "window_job", run_dir, device["platform"], workers=chips,
            window=window, minibatch=minibatch,
            records=8 * chips * per_window_task, per_task=per_window_task,
            kill=True,
        )
        phases["per_step_job"] = run_job(
            "per_step_job", run_dir, device["platform"], workers=1, window=0,
            minibatch=minibatch, records=8 * minibatch, per_task=2 * minibatch,
            kill=False,
        )
        phases["kernels"] = run_child("kernels", args.cpu)
        if chips > 1:
            phases["window_job_one_worker"] = run_job(
                "window_job_one_worker", run_dir, device["platform"],
                workers=1, window=window, minibatch=minibatch,
                records=8 * per_window_task, per_task=per_window_task,
                kill=False,
            )
            if phases["window_job_one_worker"]["chips_by_worker"]["0"] != list(
                device["chips"]
            ):
                fail("the one worker did not hold every chip")
            phases["meshes"] = run_child("meshes", args.cpu)
    except BaseException:
        # the logs outlive a failure, where the chip tool brings them back
        kept = os.path.join(ROOT, "chiprun_out", "chip_smoke")
        shutil.copytree(run_dir, kept, dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("data", "*.model"))
        say(f"logs kept in {kept}")
        raise
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)
    # the run's record, for people and CHANGES.md ...
    print(json.dumps({
        "phases": phases,
        "compile_cache_dir": compile_cache_dir(),
        "native_recordio": recordio_native() is not None,
        "native_kv": kv_native() is not None,
        "seconds": round(time.monotonic() - T0, 1),
    }))
    # ... and, last, the verdict the driver reads: these keys, no others,
    # the device as jax reported it to the probe child
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": device["platform"],
            "kind": device["device_kind"],
            "count": len(device["chips"]),
        },
    }))


if __name__ == "__main__":
    main()
