"""Shared shard-hosting machinery for the PS and KV shard groups.

Both groups (`ps_group.PSShardGroup`, `kv_group.KVShardGroup`) own N
job-lifetime service endpoints with identical lifecycles — inproc
RpcServers, subprocesses with port-file discovery, or k8s pods — and
differ only in the entry module, the servicer, and the pod builder.
The lifecycle lives HERE so a fix (port-file polling, partial-boot pod
cleanup, terminate/kill teardown) cannot drift between the two.
"""

from __future__ import annotations

import os
import subprocess
import sys
import tempfile
import time
from typing import Callable, List, Tuple


def spawn_shard_processes(
    n: int,
    entry_module: str,
    flags_fn: Callable[[int], List[str]],
    prefix: str,
    boot_timeout: float,
    shard_ids: List[int] = None,
) -> Tuple[List[subprocess.Popen], List[str]]:
    """Boot N shard subprocesses of `entry_module`; each binds an
    ephemeral port and publishes it through --port_file (no bind
    races). Returns (procs, endpoints). A boot failure reaps every
    already-spawned process BEFORE raising — the caller's procs list
    is only assigned on success, so its stop() could never see them.

    `shard_ids` overrides the identity passed to `flags_fn` and the
    chaos target stamp — the recovery plane relaunches ONE shard slot
    (e.g. shard_ids=[2]) while the default boot covers range(n)."""
    ids = list(shard_ids) if shard_ids is not None else list(range(n))
    tmp = tempfile.mkdtemp(prefix=prefix)
    procs: List[subprocess.Popen] = []
    port_files = []
    for i in ids:
        port_file = os.path.join(tmp, f"shard-{i}.port")
        port_files.append(port_file)
        argv = [
            sys.executable,
            "-m",
            entry_module,
            "--port", "0",
            "--port_file", port_file,
        ] + flags_fn(i)
        env = dict(os.environ)
        # shard math/storage is host-side; the chip belongs to the
        # workers (the entrypoints also pin the backend themselves)
        env["JAX_PLATFORMS"] = "cpu"
        # chaos scoping: "ps"/"kv"/"agg" role + shard id for an
        # inherited EDL_CHAOS_SPEC (inert when chaos is off)
        from elasticdl_tpu.rpc.chaos import chaos_env_for

        leaf = entry_module.rsplit(".", 1)[-1]
        role = "kv" if "kv" in leaf else ("agg" if "agg" in leaf else "ps")
        env.update(chaos_env_for(role, i))
        # transport tiers: EDL_TRANSPORT inherits via the env copy, but
        # the UDS socket DIR must be pinned explicitly — parent and
        # shard default to tempfile.gettempdir() independently, and a
        # TMPDIR divergence would silently strand the sockets in two
        # places (clients fall back to grpc, masking the fast path).
        from elasticdl_tpu.common.constants import ENV_UDS_DIR
        from elasticdl_tpu.rpc import transport as _transport

        env.setdefault(ENV_UDS_DIR, _transport.uds_dir())
        import elasticdl_tpu

        pkg_root = os.path.dirname(os.path.dirname(elasticdl_tpu.__file__))
        env["PYTHONPATH"] = (
            pkg_root + os.pathsep + env["PYTHONPATH"]
            if env.get("PYTHONPATH")
            else pkg_root
        )
        procs.append(subprocess.Popen(argv, env=env))
    endpoints = []
    deadline = time.time() + boot_timeout
    try:
        for k, pf in enumerate(port_files):
            while not os.path.exists(pf):
                if procs[k].poll() is not None:
                    raise RuntimeError(
                        f"shard {ids[k]} ({entry_module}) exited "
                        f"rc={procs[k].returncode} before publishing its port"
                    )
                if time.time() > deadline:
                    raise TimeoutError(
                        f"shard {ids[k]} ({entry_module}) did not publish a port"
                    )
                time.sleep(0.05)
            with open(pf) as f:
                endpoints.append(f"localhost:{int(f.read().strip())}")
    except Exception:
        stop_shard_processes(procs)
        raise
    return procs, endpoints


def stop_shard_processes(procs: List[subprocess.Popen]):
    """Terminate, grace-wait, then kill."""
    for p in procs:
        if p.poll() is None:
            p.terminate()
    for p in procs:
        try:
            p.wait(timeout=5)
        except subprocess.TimeoutExpired:
            p.kill()
