"""Master-side lifecycle manager for the embedding KV shard endpoints.

Same hosting modes and job-lifetime semantics as the dense
`PSShardGroup` (ps_group.py): ``inproc`` threads for tests/single-host,
``process`` subprocesses of ``kv_shard_main``, ``k8s`` dedicated pods.
The reference's equivalent is the Redis-cluster pod spawned at master
boot (reference: elasticdl/python/master/embedding_service.py:82-99,
:231-268) — but where a dead Redis node failed the reference's job,
this group participates in the recovery plane (master/recovery.py):
shards mirror their writes to a ring pair (`wire_mirrors`), a dead
shard is relaunched at a bumped fencing generation
(`relaunch_shard`) and its rows are restored from the pair's
mirror snapshot.
"""

from __future__ import annotations

import os
import subprocess
import time
from typing import List, Optional

from elasticdl_tpu.common.log_util import get_logger
from elasticdl_tpu.rpc.kv_client import ShardedEmbeddingStore

logger = get_logger(__name__)


class KVShardGroup:
    """Owns N embedding KV shard endpoints for one job."""

    def __init__(
        self,
        num_shards: int,
        mode: str = "inproc",
        boot_timeout: float = 60.0,
        k8s_backend=None,
    ):
        if num_shards < 1:
            raise ValueError("num_shards must be >= 1")
        if mode not in ("inproc", "process", "k8s"):
            raise ValueError(f"unknown kv group mode {mode!r}")
        if mode == "k8s" and k8s_backend is None:
            raise ValueError("k8s mode needs the cluster backend")
        self._n = num_shards
        self._mode = mode
        self._boot_timeout = boot_timeout
        self._k8s_backend = k8s_backend
        self.endpoints: List[str] = []
        # fencing generation per shard slot (rpc/fencing.py), bumped on
        # every relaunch
        self.generations: List[int] = [0] * num_shards
        self._servers = []
        # inproc servicer refs (tests/recovery read stats, drive flush)
        self.servicers = []
        self._procs: List[subprocess.Popen] = []
        self._k8s_created = 0  # pods created (>= endpoints resolved)
        self._store: Optional[ShardedEmbeddingStore] = None
        self._mirrored = False
        self._reported_dead = set()  # poll_dead dedup (dead Popen refs)

    @property
    def num_shards(self) -> int:
        return self._n

    def start(self) -> List[str]:
        if self.endpoints:
            return self.endpoints
        if self._mode == "inproc":
            self._start_inproc()
        elif self._mode == "k8s":
            for i in range(self._n):
                self._k8s_backend.create_kv_shard(
                    i, self._shard_cli_flags(i)
                )
                self._k8s_created = i + 1
            for i in range(self._n):
                self.endpoints.append(
                    self._k8s_backend.wait_kv_shard_ip(
                        i, timeout=self._boot_timeout * 5
                    )
                )
        else:
            self._start_process()
        logger.info(
            "KV shard group up (%s): %s", self._mode, ", ".join(self.endpoints)
        )
        return self.endpoints

    def _start_inproc(self):
        for i in range(self._n):
            servicer, server = self._build_inproc_shard(i)
            self.servicers.append(servicer)
            self._servers.append(server)
            self.endpoints.append(f"localhost:{server.port}")

    def _build_inproc_shard(self, i: int):
        from elasticdl_tpu.master.kv_shard import KVShardServicer
        from elasticdl_tpu.rpc.server import RpcServer

        servicer = KVShardServicer(
            i, self._n, generation=self.generations[i]
        )
        server = RpcServer(servicer.handlers(), port=0)
        servicer.attach_admission_stats(server.admission_stats)
        servicer.attach_wire_stats(server.wire)
        servicer.register_metrics()
        server.start()
        return servicer, server

    def _shard_cli_flags(self, i: int) -> List[str]:
        return [
            "--shard_id", str(i),
            "--num_shards", str(self._n),
            "--generation", str(self.generations[i]),
        ]

    def _start_process(self):
        from elasticdl_tpu.master.shard_host import spawn_shard_processes

        self._procs, self.endpoints = spawn_shard_processes(
            self._n,
            "elasticdl_tpu.master.kv_shard_main",
            self._shard_cli_flags,
            "edl_kv_",
            self._boot_timeout,
        )

    # -- replica mirroring + recovery hooks ----------------------------------

    def wire_mirrors(self):
        """Ring mirroring: shard i forwards its writes to (i+1) % N so
        every shard's rows survive on exactly one pair (needs N >= 2;
        with one shard there is nowhere to mirror). Idempotent —
        re-wiring after a relaunch re-points the ring at the new
        endpoints."""
        if self._n < 2:
            return
        from elasticdl_tpu.rpc.client import RpcClient

        for i in range(self._n):
            target = self.endpoints[(i + 1) % self._n]
            c = RpcClient(self.endpoints[i])
            try:
                c.call("KVSetMirror", {"endpoint": target}, timeout=30.0)
            finally:
                c.close()
        self._mirrored = True

    def mirror_pair_of(self, shard_id: int) -> int:
        return (int(shard_id) + 1) % self._n

    def poll_dead(self) -> List[tuple]:
        """[(shard_id, exit_code)] of process-mode shard deaths, each
        dead PROCESS reported once — keyed by the Popen object, not
        (shard, generation), for the relaunch-window reasons spelled
        out in PSShardGroup.poll_dead."""
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
        """Relaunch one KV shard slot at a bumped generation; boots
        empty — the recovery plane restores rows from the pair's
        mirror, then `wire_mirrors` re-points the ring."""
        i = int(shard_id)
        self.generations[i] += 1
        from elasticdl_tpu.obs import flight as obs_flight

        obs_flight.record(
            "generation_bump",
            shard_kind="kv",
            shard=i,
            generation=self.generations[i],
        )
        if self._mode == "inproc":
            if self._servers:
                self._servers[i].stop()
            if self.servicers:
                self.servicers[i].close()
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
                "elasticdl_tpu.master.kv_shard_main",
                self._shard_cli_flags,
                "edl_kv_",
                self._boot_timeout,
                shard_ids=[i],
            )
            self._procs[i] = procs[0]
            self.endpoints[i] = endpoints[0]
        else:  # k8s
            self._k8s_backend.delete_kv_shard(i)
            self._k8s_backend.create_kv_shard(i, self._shard_cli_flags(i))
            self.endpoints[i] = self._k8s_backend.wait_kv_shard_ip(
                i, timeout=self._boot_timeout * 5
            )
        if self._store is not None:
            self._store.update_endpoints(self.endpoints, self.generations)
        logger.info(
            "KV shard %d relaunched at generation %d on %s",
            i, self.generations[i], self.endpoints[i],
        )
        return self.endpoints[i]

    def collect_shard_metrics(self) -> dict:
        """Per-shard MetricsRegistry snapshots for the master's
        GetMetrics fleet aggregation. Inproc shards live in the
        master's process — their collectors already feed the master's
        own registry, so only out-of-process shards are polled (one
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
                out[f"kv{i}"] = resp.get("metrics", {})
            except Exception as e:  # noqa: BLE001 - scrape is best-effort
                logger.warning(
                    "kv shard %d: GetMetrics failed: %s", i, e
                )
            finally:
                c.close()
        return out

    def refence(self) -> List[int]:
        """Master-migration cutover (master/migration.py): bump every
        KV shard's fencing generation IN PLACE via KVRefence — the
        store and mirror wiring survive while the deposed master's
        stale-generation traffic starts bouncing FAILED_PRECONDITION
        (see PSShardGroup.refence for the full contract)."""
        from elasticdl_tpu.rpc.client import RpcClient

        for i, endpoint in enumerate(self.endpoints):
            target = self.generations[i] + 1
            c = RpcClient(endpoint)
            try:
                c.call("KVRefence", {"generation": target}, timeout=10.0)
            finally:
                c.close()
            self.generations[i] = target
            from elasticdl_tpu.obs import flight as obs_flight

            obs_flight.record(
                "generation_bump",
                shard_kind="kv",
                shard=i,
                generation=target,
                refence=True,
            )
        if self._store is not None:
            self._store.update_endpoints(self.endpoints, self.generations)
        logger.info(
            "KV shard group refenced: generations=%s", self.generations
        )
        return list(self.generations)

    def store(self) -> ShardedEmbeddingStore:
        """The master's store client (SparseOptimizer + checkpoints)."""
        if self._store is None:
            self._store = ShardedEmbeddingStore(
                self.endpoints, generations=self.generations
            )
            self._store.wait_ready(self._boot_timeout)
        return self._store

    def stop(self):
        if self._store is not None:
            self._store.close()
            self._store = None
        for sv in self.servicers:
            sv.close()
        self.servicers = []
        for s in self._servers:
            s.stop()
        self._servers = []
        # delete every CREATED pod, not only resolved endpoints — a
        # partially-booted group (IP wait timed out) must not leak pods
        for i in range(self._k8s_created):
            self._k8s_backend.delete_kv_shard(i)
        self._k8s_created = 0
        from elasticdl_tpu.master.shard_host import stop_shard_processes

        stop_shard_processes(self._procs)
        self._procs = []
        self.endpoints = []
