"""Master-side lifecycle manager for the sharded PS endpoints.

Two hosting modes:

- ``inproc``: shard servicers live on threads inside the master
  process behind real RPC servers on localhost — hermetic tests and
  single-host jobs (still N sockets and N locks, so worker fan-out
  parallelism is real; the GIL is released during socket IO and the
  numpy/optax slice math releases it for large arrays).
- ``process``: each shard is a subprocess of
  ``python -m elasticdl_tpu.master.ps_shard_main`` — its own
  interpreter, so PS CPU (optimizer applies, msgpack codec) scales
  with shards. The shard binds an ephemeral port and publishes it
  through ``--port_file`` (no bind races).

On Kubernetes the same entrypoint runs in dedicated PS pods (replica
type "ps", created like worker pods — see cluster/k8s_backend.py);
this manager handles the local modes, which is what the master uses
when ``--worker_backend process``.

Shards are job-lifetime services like the reference's Redis embedding
pods (reference: elasticdl/python/master/embedding_service.py:231-268
— spawned at master boot, torn down with the job), but unlike the
reference a dead shard is no longer a job failure: the recovery plane
(master/recovery.py) relaunches the slot via `relaunch_shard` at a
bumped fencing generation and restores its state from a worker
flat-buffer upload + the master's opt-state mirror. `poll_dead`
feeds process-mode shard deaths to that plane.
"""

from __future__ import annotations

import os
import subprocess
import time
from typing import List, Optional

import numpy as np

from elasticdl_tpu.common.log_util import get_logger
from elasticdl_tpu.rpc.ps_client import ShardedPS

logger = get_logger(__name__)


class PSShardGroup:
    """Owns N PS shard endpoints for one job."""

    def __init__(
        self,
        num_shards: int,
        mode: str = "inproc",
        optimizer_factory=None,  # () -> optax.GradientTransformation
        shard_argv: Optional[List[str]] = None,  # model-spec flags (process)
        grads_to_wait: int = 1,
        use_async: bool = False,
        lr_staleness_modulation: bool = False,
        staleness_window: int = 0,
        boot_timeout: float = 60.0,
        k8s_backend=None,  # K8sBackend for mode="k8s" (PS pods)
        num_workers: int = 1,
        max_inflight_syncs: int = 8,
        fanin_combine: Optional[bool] = None,
    ):
        if num_shards < 1:
            raise ValueError("num_shards must be >= 1")
        if mode not in ("inproc", "process", "k8s"):
            raise ValueError(f"unknown ps group mode {mode!r}")
        if mode in ("process", "k8s") and shard_argv is None:
            raise ValueError(f"{mode} mode needs the model-spec argv")
        if mode == "k8s" and k8s_backend is None:
            raise ValueError("k8s mode needs the cluster backend")
        self._k8s_backend = k8s_backend
        self._n = num_shards
        self._mode = mode
        self._opt_factory = optimizer_factory
        self._shard_argv = list(shard_argv or [])
        self._sync_flags = dict(
            grads_to_wait=grads_to_wait,
            use_async=use_async,
            lr_staleness_modulation=lr_staleness_modulation,
            staleness_window=staleness_window,
        )
        self._boot_timeout = boot_timeout
        self._dedup_cap = self.dedup_cap_for(num_workers, max_inflight_syncs)
        # hierarchical fan-in combining (master/fanin.py): None defers
        # to EDL_FANIN_COMBINE inside each servicer / shard process
        self._fanin_combine = fanin_combine
        self.endpoints: List[str] = []
        # fencing generation per shard SLOT, bumped on every relaunch;
        # clients stamp these as request epochs (rpc/fencing.py)
        self.generations: List[int] = [0] * num_shards
        self._servers = []  # inproc RpcServers
        # inproc servicer refs: tests/operators read stats() (e.g. the
        # chaos e2e asserts the dedup ring absorbed retried pushes)
        self.servicers = []
        self._procs: List[subprocess.Popen] = []
        self._k8s_created = 0  # pods created (>= endpoints resolved)
        self._client: Optional[ShardedPS] = None
        self._n_params = -1
        self._reported_dead = set()  # poll_dead dedup (dead Popen refs)

    @staticmethod
    def dedup_cap_for(num_workers: int, max_inflight_syncs: int = 8) -> int:
        """Dedup ring capacity: only keys whose sync is still in flight
        can legally be resent, so the ring must dominate
        num_workers x max in-flight syncs per worker (sync depth /
        step-pipeline depth) — derivation next to the retry
        classification in rpc/ps_client.py. x4 headroom covers syncs
        straddling a relaunch; the 512 floor keeps the old default for
        small jobs."""
        return max(512, int(num_workers) * int(max_inflight_syncs) * 4)

    @property
    def num_shards(self) -> int:
        return self._n

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> List[str]:
        if self.endpoints:
            return self.endpoints
        if self._mode == "inproc":
            self._start_inproc()
        elif self._mode == "k8s":
            self._start_k8s()
        else:
            self._start_process()
        logger.info(
            "PS shard group up (%s): %s", self._mode, ", ".join(self.endpoints)
        )
        return self.endpoints

    def _shard_cli_flags(self, shard_id: int) -> List[str]:
        """The shard entrypoint's own flags (shared by process/k8s)."""
        flags = [
            "--shard_id", str(shard_id),
            "--num_shards", str(self._n),
            "--generation", str(self.generations[shard_id]),
            "--dedup_cap", str(self._dedup_cap),
            "--grads_to_wait", str(self._sync_flags["grads_to_wait"]),
            "--staleness_window", str(self._sync_flags["staleness_window"]),
        ] + self._shard_argv
        if self._sync_flags["use_async"]:
            flags.append("--use_async")
        if self._sync_flags["lr_staleness_modulation"]:
            flags.append("--lr_staleness_modulation")
        if self._fanin_combine:
            flags.append("--fanin_combine")
        return flags

    def _start_k8s(self):
        """Dedicated PS pods (replica type "ps"): the shard entrypoint
        runs in its own pod and workers reach it by pod IP — the
        worker-reachable analog of the reference's Redis pod. All pods
        are created FIRST, then polled, so N slow pod schedules overlap
        instead of serializing N boot waits."""
        if hasattr(self._k8s_backend, "create_ps_shard"):
            for i in range(self._n):
                self._k8s_backend.create_ps_shard(i, self._shard_cli_flags(i))
                self._k8s_created = i + 1
            for i in range(self._n):
                self.endpoints.append(
                    self._k8s_backend.wait_ps_shard_ip(
                        i, timeout=self._boot_timeout * 5
                    )
                )
        else:  # minimal backends (tests) expose only the combined call
            for i in range(self._n):
                self.endpoints.append(
                    self._k8s_backend.start_ps_shard(
                        i, self._shard_cli_flags(i)
                    )
                )
                self._k8s_created = i + 1

    def _start_inproc(self):
        for i in range(self._n):
            servicer, server = self._build_inproc_shard(i)
            self.servicers.append(servicer)
            self._servers.append(server)
            self.endpoints.append(f"localhost:{server.port}")

    def _build_inproc_shard(self, i: int):
        from elasticdl_tpu.master.ps_optimizer import PSOptimizer
        from elasticdl_tpu.master.ps_shard import PSShardServicer
        from elasticdl_tpu.rpc.server import RpcServer

        opt = (
            PSOptimizer(self._opt_factory())
            if self._opt_factory is not None
            else None
        )
        servicer = PSShardServicer(
            i,
            self._n,
            optimizer=opt,
            generation=self.generations[i],
            dedup_cap=self._dedup_cap,
            fanin_combine=self._fanin_combine,
            **self._sync_flags,
        )
        server = RpcServer(servicer.handlers(), port=0)
        servicer.attach_wire_stats(server.wire)
        servicer.attach_admission_stats(server.admission_stats)
        servicer.register_metrics()
        server.start()
        return servicer, server

    def _start_process(self):
        from elasticdl_tpu.master.shard_host import spawn_shard_processes

        self._procs, self.endpoints = spawn_shard_processes(
            self._n,
            "elasticdl_tpu.master.ps_shard_main",
            self._shard_cli_flags,
            "edl_ps_",
            self._boot_timeout,
        )

    # -- recovery plane hooks ------------------------------------------------

    def poll_dead(self) -> List[tuple]:
        """[(shard_id, exit_code)] for process-mode shards that died
        since the last relaunch. Each dead PROCESS is reported once —
        keyed by the Popen object, not (shard, generation): relaunch
        bumps the generation before the replacement process lands in
        `_procs`, so a generation key would both re-report the old
        corpse under the new generation (relaunch storm) and consume
        the new generation's one report (a real second death would
        then go unseen). The recovery plane (master/recovery.py) polls
        this because shard subprocesses, unlike workers, have no
        pod-event stream."""
        out = []
        for i, p in enumerate(self._procs):
            if p is None or p.poll() is None:
                continue
            if p in self._reported_dead:
                continue
            self._reported_dead.add(p)
            out.append((i, p.returncode))
        return out

    def relaunch_shard(self, shard_id: int) -> str:
        """Relaunch one shard SLOT at a bumped fencing generation.
        Returns the new endpoint. The relaunched shard boots EMPTY —
        the caller (recovery plane) restores model/opt state before
        re-advertising the endpoint to workers."""
        i = int(shard_id)
        self.generations[i] += 1
        from elasticdl_tpu.obs import flight as obs_flight

        obs_flight.record(
            "generation_bump",
            shard_kind="ps",
            shard=i,
            generation=self.generations[i],
        )
        if self._mode == "inproc":
            if self._servers:
                self._servers[i].stop()
            servicer, server = self._build_inproc_shard(i)
            self.servicers[i] = servicer
            self._servers[i] = server
            self.endpoints[i] = f"localhost:{server.port}"
        elif self._mode == "process":
            from elasticdl_tpu.master.shard_host import (
                spawn_shard_processes,
                stop_shard_processes,
            )

            if self._procs and self._procs[i].poll() is None:
                stop_shard_processes([self._procs[i]])  # fence a zombie
            procs, endpoints = spawn_shard_processes(
                1,
                "elasticdl_tpu.master.ps_shard_main",
                self._shard_cli_flags,
                "edl_ps_",
                self._boot_timeout,
                shard_ids=[i],
            )
            self._procs[i] = procs[0]
            self.endpoints[i] = endpoints[0]
        else:  # k8s
            self._k8s_backend.delete_ps_shard(i)
            if hasattr(self._k8s_backend, "create_ps_shard"):
                self._k8s_backend.create_ps_shard(i, self._shard_cli_flags(i))
                self.endpoints[i] = self._k8s_backend.wait_ps_shard_ip(
                    i, timeout=self._boot_timeout * 5
                )
            else:
                self.endpoints[i] = self._k8s_backend.start_ps_shard(
                    i, self._shard_cli_flags(i)
                )
        # the master's own fan-out client must follow the move
        if self._client is not None:
            self._client.update_endpoints(self.endpoints, self.generations)
        logger.info(
            "PS shard %d relaunched at generation %d on %s",
            i, self.generations[i], self.endpoints[i],
        )
        return self.endpoints[i]

    def refence(self) -> List[int]:
        """Master-migration cutover (master/migration.py): bump every
        shard SLOT's fencing generation IN PLACE via the PSRefence RPC
        — state survives (unlike `relaunch_shard`, which boots a fresh
        empty servicer), but every client still stamping the old
        generation, the deposed master above all, bounces with
        FAILED_PRECONDITION from the moment each shard answers. The
        group's own mutable `generations` list follows so the adopting
        master's fan-out client and GetPSConfig advertise the new
        epochs. Idempotent per target: a retried cutover re-sends
        `current` which the shard treats as a no-op bump."""
        from elasticdl_tpu.rpc.client import RpcClient

        for i, endpoint in enumerate(self.endpoints):
            target = self.generations[i] + 1
            c = RpcClient(endpoint)
            try:
                c.call("PSRefence", {"generation": target}, timeout=10.0)
            finally:
                c.close()
            self.generations[i] = target
            from elasticdl_tpu.obs import flight as obs_flight

            obs_flight.record(
                "generation_bump",
                shard_kind="ps",
                shard=i,
                generation=target,
                refence=True,
            )
        if self._client is not None:
            self._client.update_endpoints(self.endpoints, self.generations)
        logger.info(
            "PS shard group refenced: generations=%s", self.generations
        )
        return list(self.generations)

    def stop(self):
        if self._client is not None:
            self._client.close()
            self._client = None
        for s in self._servers:
            s.stop()
        self._servers = []
        self.servicers = []
        # delete every CREATED pod, not only resolved endpoints — a
        # partially-booted group (IP wait timed out) must not leak pods
        for i in range(self._k8s_created):
            self._k8s_backend.delete_ps_shard(i)
        self._k8s_created = 0
        from elasticdl_tpu.master.shard_host import stop_shard_processes

        stop_shard_processes(self._procs)
        self._procs = []
        self.endpoints = []

    def collect_shard_metrics(self) -> dict:
        """Per-shard MetricsRegistry snapshots for the master's
        GetMetrics fleet aggregation. Inproc shards live in the
        master's process — their collectors already feed the master's
        own registry — so only out-of-process shards are polled (one
        best-effort GetMetrics RPC each; a dead shard contributes
        nothing rather than failing the scrape)."""
        if self._mode == "inproc":
            return {}
        from elasticdl_tpu.rpc.client import RpcClient

        out = {}
        for i, endpoint in enumerate(self.endpoints):
            c = RpcClient(endpoint)
            try:
                resp = c.call("GetMetrics", {}, timeout=10.0)
                out[f"ps{i}"] = resp.get("metrics", {})
            except Exception as e:  # noqa: BLE001 - scrape is best-effort
                logger.warning(
                    "ps shard %d: GetMetrics failed: %s", i, e
                )
            finally:
                c.close()
        return out

    # -- model plane ---------------------------------------------------------

    def client(self, n_params: Optional[int] = None) -> ShardedPS:
        if self._client is None:
            if n_params is None:
                raise RuntimeError("PS group client needs n_params once")
            self._n_params = int(n_params)
            self._client = ShardedPS(
                self.endpoints, self._n_params, generations=self.generations
            )
            self._client.wait_ready(self._boot_timeout)
        return self._client

    @property
    def initialized(self) -> bool:
        return self._client is not None

    def ensure_init(self, vec: np.ndarray, version: int = 0) -> List[int]:
        """Idempotent model init (shard-side SETNX)."""
        vec = np.asarray(vec, dtype=np.float32)
        return self.client(vec.size).init_model(vec, version)

    def export_opt(self):
        """Per-shard optimizer-state leaves for checkpoints."""
        if self._client is None:
            return None
        return self._client.export_opt()

    def restore_opt(self, shards):
        """Adopt checkpointed per-shard optimizer state (after
        ensure_init). Requires the same shard count as the
        checkpointing job — slices don't re-split."""
        self.client().restore_opt(shards)

    def assemble(self, model_dtype: Optional[str] = None):
        """(shard_versions, full_flat_vec) — the master's view for
        checkpoints/eval snapshots; slices are pulled concurrently and
        may straddle a step (relaxed snapshot, see ps_shard.py)."""
        if self._client is None:
            raise RuntimeError("PS group not initialized")
        return self._client.pull(model_dtype=model_dtype)
