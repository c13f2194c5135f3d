"""Master-side lifecycle manager for the aggregator nodes.

Mirrors PSShardGroup's two local hosting modes (master/ps_group.py):
``inproc`` threads for hermetic tests, ``process`` subprocesses of
``python -m elasticdl_tpu.agg.agg_main`` for real deployments (on
Kubernetes the same entrypoint would run one aggregator pod per worker
host; the local modes are what the master drives here).

Unlike a PS shard, an aggregator holds no model state: `relaunch_shard`
bumps the slot's fencing generation and boots a FRESH node — there is
no restore step, and the recovery plane advertises the new endpoint as
soon as the port file lands (relaunch-not-restore,
master/recovery.py). `update_upstream` re-points every live node at a
new PS endpoint list after a PS relaunch.
"""

from __future__ import annotations

import subprocess
from typing import List, Optional

from elasticdl_tpu.common.log_util import get_logger

logger = get_logger(__name__)


class AggGroup:
    """Owns H aggregator endpoints for one job."""

    def __init__(
        self,
        num_aggs: int,
        ps_endpoints: List[str],
        mode: str = "inproc",
        boot_timeout: float = 60.0,
    ):
        if num_aggs < 1:
            raise ValueError("num_aggs must be >= 1")
        if mode not in ("inproc", "process"):
            raise ValueError(f"unknown agg group mode {mode!r}")
        self._n = num_aggs
        self._mode = mode
        self._ps_endpoints = list(ps_endpoints)
        self._boot_timeout = boot_timeout
        self.endpoints: List[str] = []
        # fencing generation per aggregator SLOT, bumped on relaunch;
        # workers stamp these as AggPushDelta epochs (rpc/fencing.py)
        self.generations: List[int] = [0] * num_aggs
        self._servers = []  # inproc RpcServers
        self.servicers = []  # inproc servicer refs (tests read stats())
        self._procs: List[subprocess.Popen] = []
        self._reported_dead = set()  # poll_dead dedup (dead Popen refs)

    @property
    def num_aggs(self) -> int:
        return self._n

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> List[str]:
        if self.endpoints:
            return self.endpoints
        if self._mode == "inproc":
            for i in range(self._n):
                servicer, server = self._build_inproc(i)
                self.servicers.append(servicer)
                self._servers.append(server)
                self.endpoints.append(f"localhost:{server.port}")
        else:
            from elasticdl_tpu.master.shard_host import spawn_shard_processes

            self._procs, self.endpoints = spawn_shard_processes(
                self._n,
                "elasticdl_tpu.agg.agg_main",
                self._cli_flags,
                "edl_agg_",
                self._boot_timeout,
            )
        logger.info(
            "aggregator group up (%s): %s",
            self._mode,
            ", ".join(self.endpoints),
        )
        return self.endpoints

    def _cli_flags(self, agg_id: int) -> List[str]:
        flags = [
            "--agg_id", str(agg_id),
            "--generation", str(self.generations[agg_id]),
            "--ps_endpoints", ",".join(self._ps_endpoints),
        ]
        return flags

    def _build_inproc(self, i: int):
        from elasticdl_tpu.agg.aggregator import AggregatorServicer
        from elasticdl_tpu.rpc.server import RpcServer

        servicer = AggregatorServicer(
            i,
            self._ps_endpoints,
            generation=self.generations[i],
        )
        server = RpcServer(servicer.handlers(), port=0)
        servicer.attach_wire_stats(server.wire)
        servicer.attach_admission_stats(server.admission_stats)
        servicer.register_metrics()
        server.start()
        return servicer, server

    def pid_of(self, agg_id: int) -> Optional[int]:
        """Live pid of a process-mode node, None otherwise (inproc,
        dead, or not yet booted). Fault injectors (chaos/scenario.py
        kill_host) go through this instead of reaching into _procs."""
        i = int(agg_id)
        if self._mode != "process" or i >= len(self._procs):
            return None
        p = self._procs[i]
        if p is None or p.poll() is not None:
            return None
        return p.pid

    # -- recovery plane hooks ------------------------------------------------

    def poll_dead(self) -> List[tuple]:
        """[(agg_id, exit_code)] for process-mode nodes that died since
        the last relaunch; one report per dead PROCESS, keyed by the
        Popen object (same rationale as PSShardGroup.poll_dead)."""
        out = []
        for i, p in enumerate(self._procs):
            if p is None or p.poll() is None:
                continue
            if p in self._reported_dead:
                continue
            self._reported_dead.add(p)
            out.append((i, p.returncode))
        return out

    def relaunch_shard(self, agg_id: int) -> str:
        """Relaunch one aggregator SLOT at a bumped fencing generation
        and return the new endpoint. No restore: the node is stateless,
        so the replacement is serviceable the moment it binds."""
        i = int(agg_id)
        self.generations[i] += 1
        from elasticdl_tpu.obs import flight as obs_flight

        obs_flight.record(
            "generation_bump",
            shard_kind="agg",
            shard=i,
            generation=self.generations[i],
        )
        if self._mode == "inproc":
            if self._servers:
                self.servicers[i].close()
                self._servers[i].stop()
            servicer, server = self._build_inproc(i)
            self.servicers[i] = servicer
            self._servers[i] = server
            self.endpoints[i] = f"localhost:{server.port}"
        else:
            from elasticdl_tpu.master.shard_host import (
                spawn_shard_processes,
                stop_shard_processes,
            )

            if self._procs and self._procs[i].poll() is None:
                stop_shard_processes([self._procs[i]])  # fence a zombie
            procs, endpoints = spawn_shard_processes(
                1,
                "elasticdl_tpu.agg.agg_main",
                self._cli_flags,
                "edl_agg_",
                self._boot_timeout,
                shard_ids=[i],
            )
            self._procs[i] = procs[0]
            self.endpoints[i] = endpoints[0]
        logger.info(
            "aggregator %d relaunched at generation %d on %s",
            i, self.generations[i], self.endpoints[i],
        )
        return self.endpoints[i]

    def update_upstream(self, ps_endpoints: List[str]) -> None:
        """Re-point every node at a new PS endpoint list (after a PS
        relaunch moved a shard). Best-effort per node: a node that is
        down will be relaunched with the fresh list anyway
        (`_cli_flags` / `_build_inproc` read `self._ps_endpoints`)."""
        self._ps_endpoints = list(ps_endpoints)
        from elasticdl_tpu.rpc.client import RpcClient

        for i, endpoint in enumerate(self.endpoints):
            c = RpcClient(endpoint)
            try:
                c.call(
                    "AggUpdateUpstream",
                    {
                        "endpoints": self._ps_endpoints,
                        "epoch": self.generations[i],
                    },
                    timeout=10.0,
                )
            except Exception as e:  # noqa: BLE001 - node may be mid-relaunch
                logger.warning(
                    "aggregator %d: upstream re-point failed: %s", i, e
                )
            finally:
                c.close()

    def stats(self) -> dict:
        """Per-node counter snapshot for the obs/bench surface. Inproc
        nodes are read directly; process nodes answer one best-effort
        AggStats RPC each (a dead node contributes nothing rather than
        failing the scrape — poll_dead() is the liveness surface)."""
        if self._mode == "inproc":
            return {
                f"agg{i}": s.stats()
                for i, s in enumerate(self.servicers)
            }
        from elasticdl_tpu.rpc.client import RpcClient

        out = {}
        for i, endpoint in enumerate(self.endpoints):
            c = RpcClient(endpoint)
            try:
                out[f"agg{i}"] = c.call("AggStats", {}, timeout=10.0)
            except Exception as e:  # noqa: BLE001 - scrape is best-effort
                logger.warning(
                    "aggregator %d: AggStats failed: %s", i, e
                )
            finally:
                c.close()
        return out

    def stop(self):
        for s in self.servicers:
            if hasattr(s, "close"):
                s.close()
        for s in self._servers:
            s.stop()
        self._servers = []
        self.servicers = []
        from elasticdl_tpu.master.shard_host import stop_shard_processes

        stop_shard_processes(self._procs)
        self._procs = []
        self.endpoints = []
