"""Chaos-injection layer tests: the FaultPlan spec/scoping/determinism
unit tier, interceptor behavior against real RPC endpoints, the
worker-manager response to EXIT_CODE_MASTER_UNREACHABLE, and the
chaos e2e — a real ProcessBackend training job under injected latency,
UNAVAILABLE errors, dropped responses, and a worker crash, asserting
convergence with EXACT task/gradient accounting against a fault-free
same-seed run."""

import json
import os
import subprocess
import sys
import time

import grpc
import numpy as np
import pytest

from elasticdl_tpu.rpc import chaos
from elasticdl_tpu.common.constants import (
    ENV_CHAOS_ROLE as ENV_ROLE,
    ENV_CHAOS_SPEC as ENV_SPEC,
    ENV_CHAOS_TARGET_ID as ENV_TARGET,
)
from elasticdl_tpu.rpc.chaos import (
    CHAOS_CRASH_EXIT_CODE,
    FaultPlan,
    InjectedRpcError,
    chaos_env_for,
)
from elasticdl_tpu.rpc.client import RpcClient
from elasticdl_tpu.rpc.policy import RetryPolicy
from elasticdl_tpu.rpc.server import RpcServer

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def fast_policy(**kw):
    kw.setdefault("initial_backoff", 0.01)
    kw.setdefault("max_backoff", 0.05)
    return RetryPolicy(**kw)


# -- FaultPlan construction and scoping --------------------------------------


def test_from_env_inline_spec(monkeypatch):
    spec = {"seed": 9, "faults": [{"kind": "latency", "latency_ms": 5}]}
    monkeypatch.setenv(ENV_SPEC, json.dumps(spec))
    monkeypatch.setenv(ENV_ROLE, "worker")
    monkeypatch.setenv(ENV_TARGET, "3")
    plan = FaultPlan.from_env()
    assert plan is not None
    assert (plan.seed, plan.role, plan.target_id) == (9, "worker", "3")
    assert plan.faults[0].kind == "latency"


def test_from_env_file_spec(monkeypatch, tmp_path):
    path = tmp_path / "spec.json"
    path.write_text(json.dumps({"faults": [{"kind": "drop"}]}))
    monkeypatch.setenv(ENV_SPEC, f"@{path}")
    plan = FaultPlan.from_env()
    assert plan is not None and plan.faults[0].kind == "drop"


def test_from_env_absent_or_malformed_is_off(monkeypatch):
    monkeypatch.delenv(ENV_SPEC, raising=False)
    assert FaultPlan.from_env() is None
    # a malformed spec must never take down a training process
    monkeypatch.setenv(ENV_SPEC, "{not json")
    assert FaultPlan.from_env() is None
    monkeypatch.setenv(ENV_SPEC, "@/nonexistent/spec.json")
    assert FaultPlan.from_env() is None
    # unknown kinds are a spec bug -> also chaos-off, not a crash
    monkeypatch.setenv(
        ENV_SPEC, json.dumps({"faults": [{"kind": "explode"}]})
    )
    assert FaultPlan.from_env() is None


def test_role_and_target_scoping():
    spec = {
        "faults": [
            {"kind": "drop", "roles": ["worker"], "targets": ["0"]},
        ]
    }
    hit = FaultPlan.from_spec(spec, role="worker", target_id="0")
    wrong_target = FaultPlan.from_spec(spec, role="worker", target_id="2")
    wrong_role = FaultPlan.from_spec(spec, role="ps", target_id="0")
    assert hit.actions_for("M", "client")
    assert not wrong_target.actions_for("M", "client")
    assert not wrong_role.actions_for("M", "client")


def test_method_and_side_scoping():
    spec = {"faults": [{"kind": "drop", "methods": ["PSPull"], "side": "server"}]}
    plan = FaultPlan.from_spec(spec)
    assert not plan.actions_for("PSPull", "client")
    assert not plan.actions_for("PSPushGrad", "server")
    assert plan.actions_for("PSPull", "server")


def test_nth_every_and_max_fires():
    plan = FaultPlan.from_spec(
        {
            "faults": [
                {"kind": "drop", "nth": 3},
                {"kind": "latency", "every": 2, "max_fires": 2},
            ]
        }
    )
    kinds = [
        tuple(f.kind for f in plan.actions_for("M", "client"))
        for _ in range(8)
    ]
    # nth=3 fires exactly once, on call 3; every=2 fires on calls
    # 2 and 4 then hits max_fires
    assert kinds == [
        (), ("latency",), ("drop",), ("latency",), (), (), (), (),
    ]


def test_probabilistic_firing_is_deterministic():
    spec = {"seed": 5, "faults": [{"kind": "drop", "prob": 0.4}]}
    a = FaultPlan.from_spec(spec)
    b = FaultPlan.from_spec(spec)
    pat_a = [bool(a.actions_for("M", "client")) for _ in range(60)]
    pat_b = [bool(b.actions_for("M", "client")) for _ in range(60)]
    assert pat_a == pat_b, "same spec must fire identically"
    assert 0 < sum(pat_a) < 60, "prob 0.4 over 60 calls fires some, not all"
    c = FaultPlan.from_spec({"seed": 6, "faults": [{"kind": "drop", "prob": 0.4}]})
    pat_c = [bool(c.actions_for("M", "client")) for _ in range(60)]
    assert pat_a != pat_c, "a different seed must reshuffle the firing"


def test_once_file_fires_for_exactly_one_plan(tmp_path):
    """The cross-process crash latch: two processes (modeled as two
    plans) race on the same once_file; exactly one fires."""
    latch = str(tmp_path / "crash.once")
    spec = {"faults": [{"kind": "error", "nth": 1, "once_file": latch}]}
    first = FaultPlan.from_spec(spec)
    second = FaultPlan.from_spec(spec)
    assert first.actions_for("M", "client")
    assert not second.actions_for("M", "client")
    assert os.path.exists(latch)


def test_chaos_env_for():
    assert chaos_env_for("worker", 4) == {ENV_ROLE: "worker", ENV_TARGET: "4"}
    assert chaos_env_for("ps") == {ENV_ROLE: "ps"}


# -- interceptors against real RPC endpoints ---------------------------------


def _echo_server(hits, fault_plan=None):
    def echo(req):
        hits.append(req.get("x"))
        return {"x": req.get("x")}

    server = RpcServer({"Echo": echo}, port=0, fault_plan=fault_plan)
    server.start()
    return server


def test_client_error_injection_retried_to_success():
    hits = []
    server = _echo_server(hits)
    try:
        plan = FaultPlan.from_spec(
            {"faults": [{"kind": "error", "methods": ["Echo"], "nth": 1}]}
        )
        client = RpcClient(
            f"localhost:{server.port}", policy=fast_policy(), fault_plan=plan
        )
        client.wait_ready(10)
        # injected UNAVAILABLE happens before the send; the retry lands
        assert client.call("Echo", {"x": 1}, timeout=10, idempotent=True) == {
            "x": 1
        }
        assert hits == [1], "first attempt must never have reached the server"
        client.close()
    finally:
        server.stop()


def test_client_error_surfaces_on_non_idempotent():
    hits = []
    server = _echo_server(hits)
    try:
        plan = FaultPlan.from_spec(
            {"faults": [{"kind": "error", "methods": ["Echo"], "nth": 1}]}
        )
        client = RpcClient(
            f"localhost:{server.port}", policy=fast_policy(), fault_plan=plan
        )
        client.wait_ready(10)
        with pytest.raises(InjectedRpcError) as ei:
            client.call("Echo", {"x": 1}, timeout=10, idempotent=False)
        assert ei.value.code() == grpc.StatusCode.UNAVAILABLE
        assert hits == [], "non-idempotent call must not be retried"
        client.close()
    finally:
        server.stop()


def test_drop_applies_server_side_then_retry_dedupes():
    """The nastiest shape: the server APPLIES the call, the client sees
    UNAVAILABLE. The retry must reach the server again — which is
    exactly why mutating ops carry report_keys for server-side dedup."""
    hits = []
    server = _echo_server(hits)
    try:
        plan = FaultPlan.from_spec(
            {"faults": [{"kind": "drop", "methods": ["Echo"], "nth": 1}]}
        )
        client = RpcClient(
            f"localhost:{server.port}", policy=fast_policy(), fault_plan=plan
        )
        client.wait_ready(10)
        assert client.call("Echo", {"x": 7}, timeout=10, idempotent=True) == {
            "x": 7
        }
        assert hits == [7, 7], "dropped call was applied, then retried"
        client.close()
    finally:
        server.stop()


@pytest.mark.chaos
def test_bucketed_super_window_replay_absorbed_by_dedup():
    """Drop-retry parity for the bucketed push: buckets of one
    super-window share ONE lineage key (report_key), so every replay
    shape must land on exact fault-free versions:

    (a) a PARKED part's response is lost — the retry overwrites its
        slot idempotently and the stream completes (no dedup hit: the
        set had not applied);
    (b) the COMPLETING part's response is lost — the set applied, so
        the retried part (a PARTIAL re-send of the set) must hit the
        report_key dedup ring, not re-apply;
    (c) the whole super-window replays under the same key (the
        spawn-retry shape) — every part dedups, versions do not move,
        and no ghost parked set is left behind."""
    from elasticdl_tpu.master.ps_group import PSShardGroup
    from elasticdl_tpu.rpc.ps_client import ShardedPS

    bounds = [0, 2, 5, 10]  # layer-aligned cuts crossing shard bounds

    def blip_shard_1(ps, group, nth):
        ps._clients[1].close()
        ps._clients[1] = RpcClient(
            group.endpoints[1],
            policy=fast_policy(),
            fault_plan=FaultPlan.from_spec(
                {"faults": [{"kind": "drop",
                             "methods": ["PSPushDeltaBucket"],
                             "nth": nth}]}
            ),
        )

    group = PSShardGroup(3, mode="inproc")
    group.start()
    try:
        group.ensure_init(np.zeros(10, np.float32), version=0)
        ps = ShardedPS(group.endpoints, 10)

        # (a) shard 1's FIRST part applies (parks) but the response is
        # lost: the retry re-parks idempotently, the stream completes
        blip_shard_1(ps, group, 1)
        versions, _ = ps.push_delta_bucketed(
            np.ones(10, np.float32), 2, [0, 0, 0], bounds,
            report_key="sw0",
        )
        assert versions == [2, 2, 2], f"torn after parked drop: {versions}"
        _, vec = ps.pull()
        np.testing.assert_allclose(vec, 1.0)
        assert group.servicers[1].stats()["duplicate_pushes"] == 0

        # (b) shard 1's LAST part completes the set, response lost: the
        # retry must dedup on the shared lineage key, not double-apply
        blip_shard_1(ps, group, 2)
        versions, _ = ps.push_delta_bucketed(
            np.ones(10, np.float32), 2, [2, 2, 2], bounds,
            report_key="sw1",
        )
        assert versions == [4, 4, 4], f"torn after apply drop: {versions}"
        _, vec = ps.pull()
        np.testing.assert_allclose(vec, 2.0)  # applied exactly once
        assert group.servicers[1].stats()["duplicate_pushes"] >= 1

        # (c) full replay under the same lineage key with a PARTIAL
        # part set re-sent: every part dedups, versions stay exact
        before = [sv.stats()["duplicate_pushes"] for sv in group.servicers]
        versions, _ = ps.push_delta_bucketed(
            np.ones(10, np.float32), 2, [2, 2, 2], bounds,
            report_key="sw1",
        )
        assert versions == [4, 4, 4], f"replay moved versions: {versions}"
        _, vec = ps.pull()
        np.testing.assert_allclose(vec, 2.0)
        after = [sv.stats()["duplicate_pushes"] for sv in group.servicers]
        assert all(b > a for a, b in zip(before, after))
        assert all(
            sv.stats()["parked_bucket_sets"] == 0 for sv in group.servicers
        ), "replayed parts must not park a ghost set"
        ps.close()
    finally:
        group.stop()


def test_server_side_error_injection_retried():
    hits = []
    plan = FaultPlan.from_spec(
        {
            "faults": [
                {"kind": "error", "methods": ["Echo"], "side": "server",
                 "nth": 1, "code": "UNAVAILABLE"}
            ]
        }
    )
    server = _echo_server(hits, fault_plan=plan)
    try:
        client = RpcClient(f"localhost:{server.port}", policy=fast_policy())
        client.wait_ready(10)
        assert client.call("Echo", {"x": 2}, timeout=10, idempotent=True) == {
            "x": 2
        }
        assert hits == [2], "abort happened before the handler ran"
        client.close()
    finally:
        server.stop()


def test_latency_injection_delays_the_call():
    hits = []
    server = _echo_server(hits)
    try:
        plan = FaultPlan.from_spec(
            {"faults": [{"kind": "latency", "methods": ["Echo"],
                         "latency_ms": 80, "nth": 1}]}
        )
        client = RpcClient(f"localhost:{server.port}", fault_plan=plan)
        client.wait_ready(10)
        t0 = time.monotonic()
        client.call("Echo", {"x": 3}, timeout=10)
        assert time.monotonic() - t0 >= 0.08
        client.close()
    finally:
        server.stop()


def test_crash_fault_kills_the_process_with_chaos_exit_code(tmp_path):
    """End-to-end crash path in a real subprocess: the child's RpcClient
    picks the spec up from the environment (the production activation
    path) and `crash when=after` must exit CHAOS_CRASH_EXIT_CODE with
    the call APPLIED server-side."""
    hits = []
    server = _echo_server(hits)
    try:
        import elasticdl_tpu

        pkg_root = os.path.dirname(os.path.dirname(elasticdl_tpu.__file__))
        env = dict(os.environ)
        env["PYTHONPATH"] = pkg_root
        env["JAX_PLATFORMS"] = "cpu"
        env[ENV_SPEC] = json.dumps(
            {
                "faults": [
                    {"kind": "crash", "methods": ["Echo"], "roles": ["worker"],
                     "nth": 1, "when": "after"}
                ]
            }
        )
        env.update(chaos_env_for("worker", 0))
        child = (
            "from elasticdl_tpu.rpc.client import RpcClient\n"
            f"c = RpcClient('localhost:{server.port}')\n"
            "c.wait_ready(10)\n"
            "c.call('Echo', {'x': 9}, timeout=10)\n"
            "print('survived')\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", child],
            env=env,
            capture_output=True,
            text=True,
            timeout=60,
        )
        assert proc.returncode == CHAOS_CRASH_EXIT_CODE, proc.stderr
        assert "survived" not in proc.stdout
        assert hits == [9], "crash-after must fire with the call applied"
    finally:
        server.stop()


# -- worker-manager handling of the unreachable exit code --------------------


def test_master_unreachable_exit_is_relaunch_eligible():
    """A worker that exits EXIT_CODE_MASTER_UNREACHABLE (graceful
    degradation, not a crash) must get its in-flight tasks requeued and
    a replacement launched — unlike EXIT_CODE_JOB_FAILED, which is
    terminal by design."""
    from elasticdl_tpu.cluster.pod_backend import PodBackend, PodEvent, PodPhase
    from elasticdl_tpu.common.constants import (
        EXIT_CODE_JOB_FAILED,
        EXIT_CODE_MASTER_UNREACHABLE,
    )
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.master.worker_manager import WorkerManager

    class FakeBackend(PodBackend):
        def __init__(self):
            self.started = []
            self._cb = None

        def set_event_callback(self, cb):
            self._cb = cb

        def start_worker(self, worker_id, argv, envs):
            self.started.append(worker_id)

        def delete_worker(self, worker_id):
            pass

        def stop(self):
            pass

        def fire(self, worker_id, exit_code):
            self._cb(PodEvent(worker_id, PodPhase.FAILED, exit_code=exit_code))

    dispatcher = TaskDispatcher({"f": 64}, {}, {}, 16, 1)
    backend = FakeBackend()
    manager = WorkerManager(
        backend,
        dispatcher,
        num_workers=2,
        worker_argv_fn=lambda wid: [],
        max_relaunches=4,
    )
    manager.start_workers()
    assert dispatcher.get(0) is not None
    before = dispatcher.pending_count()
    backend.fire(0, EXIT_CODE_MASTER_UNREACHABLE)
    assert dispatcher.pending_count() == before + 1, "task not recovered"
    assert backend.started == [0, 1, 2], "no replacement launched"
    assert manager.relaunches() == 1
    # contrast: a worker that exits JOB_FAILED is NOT replaced
    backend.fire(1, EXIT_CODE_JOB_FAILED)
    assert backend.started == [0, 1, 2]


# -- the chaos e2e -----------------------------------------------------------


def _grep_logs(log_dir, needle):
    count = 0
    for name in os.listdir(log_dir):
        with open(os.path.join(log_dir, name), errors="replace") as f:
            count += f.read().count(needle)
    return count


def _run_training_job(tmp, tag, monkeypatch, chaos_spec):
    """One ProcessBackend sync-SGD job (2 workers, 2 inproc PS shards,
    per-step gradient pushes). Returns the accounting the chaos test
    compares across runs."""
    from elasticdl_tpu.cluster.pod_backend import ProcessBackend
    from elasticdl_tpu.common.args import master_parser, worker_forward_args
    from elasticdl_tpu.master.main import build_master
    from elasticdl_tpu.master.worker_manager import WorkerManager

    if chaos_spec is None:
        monkeypatch.delenv(ENV_SPEC, raising=False)
    else:
        monkeypatch.setenv(ENV_SPEC, json.dumps(chaos_spec))
    args = master_parser().parse_args(
        [
            "--model_zoo", FIXTURES,
            "--model_def", "linear_module.custom_model",
            "--minibatch_size", "16",
            "--training_data_dir", tmp,
            "--records_per_task", "32",
            "--num_epochs", "2",
            "--grads_to_wait", "1",
            "--num_workers", "2",
            "--worker_backend", "process",
            "--num_ps", "2",
            "--ps_mode", "inproc",
            "--staleness_window", "1",
            # two workers applying every push as it lands work one
            # version behind each other: at the fixture's default
            # step (0.5) the bias sits on the stability edge of a
            # one-step-delayed update and rings for the whole job
            # whenever both workers start in the same instant
            "--optimizer", "optimizer_delayed",
        ]
    )
    _spec, dispatcher, servicer, _evs, _ckpt = build_master(args, "training")
    server = RpcServer(servicer.handlers(), port=0)
    server.start()
    addr = f"localhost:{server.port}"
    log_dir = os.path.join(tmp, f"logs-{tag}")
    backend = ProcessBackend(log_dir=log_dir)
    manager = WorkerManager(
        backend,
        dispatcher,
        num_workers=2,
        worker_argv_fn=lambda wid: worker_forward_args(args, wid, addr),
        envs={"JAX_PLATFORMS": "cpu"},
        max_relaunches=4,
    )
    manager.start_workers()
    try:
        deadline = time.time() + 300
        while not dispatcher.finished():
            assert time.time() < deadline, f"job[{tag}] stuck"
            assert not manager.all_exited(), f"job[{tag}]: all workers gone"
            time.sleep(0.05)
        assert not dispatcher.has_failed_tasks()
        if any(f["kind"] == "crash" for f in (chaos_spec or {}).get("faults", ())):
            # the whole job is about a second of RPCs once both workers
            # are up (less on the local carrier), so the survivor can
            # finish it before the victim has made the GetTask it dies
            # on, or before the manager has seen it die: the crash and
            # its relaunch are part of what this run must show, so it
            # ends when they have happened, not a poll earlier; on a
            # clock of its own, so a job that a loaded host stretched
            # towards its deadline leaves the relaunch its time
            deadline = time.time() + 120
            while manager.relaunches() < 1:
                assert time.time() < deadline, f"job[{tag}]: no relaunch"
                time.sleep(0.05)
        params, _aux, _version = servicer.get_params_copy()
        stats = [sv.stats() for sv in servicer.ps_group.servicers]
        return {
            "completed_records": dispatcher.completed_records(),
            "versions": [s["version"] for s in stats],
            "applied": sum(s["applied_pushes"] for s in stats),
            "duplicates": sum(s["duplicate_pushes"] for s in stats),
            "relaunches": manager.relaunches(),
            "kernel": float(
                np.asarray(params["Dense_0"]["kernel"]).ravel()[0]
            ),
            "log_dir": log_dir,
            # which transport tiers the workers actually reached the
            # master over (the UDS-tier variant pins this)
            "server_transports": server.wire_stats().get("transports", {}),
        }
    finally:
        manager.stop_relaunch_and_remove_workers()
        backend.stop()
        server.stop()
        if servicer.ps_group is not None:
            servicer.ps_group.stop()


@pytest.mark.e2e
@pytest.mark.chaos
def test_chaos_training_job_exact_accounting(tmp_path, monkeypatch):
    """The acceptance test: inject latency + UNAVAILABLE errors +
    dropped responses + a worker crash into a real ProcessBackend
    training run. The job must converge with EXACT accounting — every
    task completed exactly once, every retried gradient push absorbed
    by the report_key dedup ring — and finish at the IDENTICAL final
    shard versions as a fault-free run of the same seed/fixture.

    The fixture: 2 files x 64 records x 2 epochs / minibatch 16 =
    16 gradient pushes per shard; grads_to_wait=1 applies each push,
    so the fault-free final version of every shard is exactly 16."""
    from elasticdl_tpu.testing import write_linear_records

    tmp = str(tmp_path)
    for i in range(2):
        write_linear_records(
            os.path.join(tmp, f"shard-{i}.rio"), 64, seed=i, noise=0.05
        )
    chaos_spec = {
        "seed": 11,
        "faults": [
            # slow shard: deterministic added latency on model pulls
            {"kind": "latency", "methods": ["PSPull"], "roles": ["worker"],
             "latency_ms": 20, "every": 1, "max_fires": 4},
            # flaky network: periodic UNAVAILABLE before the send
            {"kind": "error", "code": "UNAVAILABLE",
             "methods": ["PSPushGrad"], "roles": ["worker"], "every": 4,
             "max_fires": 3},
            # lost response: the push APPLIES, the worker must retry and
            # the shard's dedup ring must absorb the resend
            {"kind": "drop", "methods": ["PSPushGrad"], "roles": ["worker"],
             "nth": 3},
            # process death mid-job: worker 0 dies right after being
            # ASSIGNED its second task (never processed); recover_tasks
            # must requeue it and a replacement must finish the job.
            # targets+once_file keep the replacement from dying too.
            {"kind": "crash", "methods": ["GetTask"], "roles": ["worker"],
             "targets": ["0"], "nth": 2, "when": "after",
             "once_file": os.path.join(tmp, "crash.once")},
        ],
    }
    under_chaos = _run_training_job(tmp, "chaos", monkeypatch, chaos_spec)
    fault_free = _run_training_job(tmp, "clean", monkeypatch, None)

    # every record processed exactly once, in both runs
    assert under_chaos["completed_records"] == 256
    assert fault_free["completed_records"] == 256
    # the crash actually happened and was recovered by a relaunch
    assert under_chaos["relaunches"] >= 1
    assert os.path.exists(os.path.join(tmp, "crash.once"))
    # the dropped-response retries were absorbed, not double-applied:
    # final shard versions are IDENTICAL to the fault-free run
    assert under_chaos["versions"] == fault_free["versions"] == [16, 16]
    assert under_chaos["duplicates"] >= 1, "no drop-retry was deduped"
    assert under_chaos["applied"] == fault_free["applied"] == 32
    # all four fault kinds demonstrably fired inside the workers
    assert _grep_logs(under_chaos["log_dir"], "chaos: +20ms latency") >= 1
    assert _grep_logs(under_chaos["log_dir"], "chaos: injecting UNAVAILABLE") >= 1
    assert _grep_logs(under_chaos["log_dir"], "chaos: dropping response") >= 1
    assert _grep_logs(under_chaos["log_dir"], "chaos: crashing process") == 1
    # the fault-free run saw no chaos at all
    assert _grep_logs(fault_free["log_dir"], "chaos:") == 0
    # and the model still converged (y = 2x + 1 fixture)
    assert abs(under_chaos["kernel"] - 2.0) < 0.6, under_chaos["kernel"]


@pytest.mark.e2e
@pytest.mark.chaos
@pytest.mark.parametrize("mode", ["uds", "unset"])
def test_chaos_exact_accounting_over_the_local_carrier(
    mode, tmp_path, monkeypatch
):
    """The acceptance run again, but with every localhost RPC routed
    over the Unix-domain-socket carrier: named (EDL_TRANSPORT=uds
    inherits into the spawned workers) and as what a local peer gets
    with nothing set. Faults inject at the UDS framing layer
    (transport_faults_before/after) instead of gRPC interceptors, and
    the accounting bar is the same absolute one: every record exactly
    once, dedup absorbing the drop-retry, shard versions landing at
    [16, 16]. Uses real subprocess workers — the crash fault's
    os._exit must kill a worker, not the test process, so the inproc
    tier is deliberately NOT exercised here (it has no process
    boundary and no crash surface). Teardown is part of the carrier's
    contract: the job leaves no socket file behind."""
    from elasticdl_tpu.common.constants import ENV_TRANSPORT, ENV_UDS_DIR
    from elasticdl_tpu.testing import write_linear_records

    tmp = str(tmp_path)
    for i in range(2):
        write_linear_records(
            os.path.join(tmp, f"shard-{i}.rio"), 64, seed=i, noise=0.05
        )
    if mode == "unset":
        monkeypatch.delenv(ENV_TRANSPORT, raising=False)
    else:
        monkeypatch.setenv(ENV_TRANSPORT, mode)
    monkeypatch.setenv(ENV_UDS_DIR, tmp)
    chaos_spec = {
        "seed": 11,
        "faults": [
            {"kind": "error", "code": "UNAVAILABLE",
             "methods": ["PSPushGrad"], "roles": ["worker"], "every": 4,
             "max_fires": 3},
            {"kind": "drop", "methods": ["PSPushGrad"], "roles": ["worker"],
             "nth": 3},
            {"kind": "crash", "methods": ["GetTask"], "roles": ["worker"],
             "targets": ["0"], "nth": 2, "when": "after",
             "once_file": os.path.join(tmp, "crash.once")},
        ],
    }
    result = _run_training_job(tmp, f"{mode}-chaos", monkeypatch, chaos_spec)
    # exact accounting: identical absolute numbers to the fault-free
    # gRPC baseline in test_chaos_training_job_exact_accounting
    assert result["completed_records"] == 256
    assert result["versions"] == [16, 16]
    assert result["applied"] == 32
    assert result["duplicates"] >= 1, "no drop-retry was deduped"
    assert result["relaunches"] >= 1
    assert abs(result["kernel"] - 2.0) < 0.6, result["kernel"]
    # the fast path actually carried the job: the master saw worker
    # calls over uds and none over grpc (no silent fallback)
    tiers = result["server_transports"]
    assert tiers.get("uds", {}).get("calls", 0) > 0, tiers
    assert tiers.get("grpc", {}).get("calls", 0) == 0, tiers
    assert not [f for f in os.listdir(tmp) if f.startswith("edl-uds-")]


@pytest.mark.e2e
@pytest.mark.chaos
def test_sigkill_shard_leaves_a_socket_file_the_relaunch_sweeps(
    tmp_path, monkeypatch
):
    """Dead-listener reclamation, end to end: SIGKILL a PS shard
    subprocess serving over its Unix socket (no atexit, no finally —
    its socket file stays, with nothing listening behind it), relaunch
    the slot at a bumped fencing generation, and assert the
    successor's boot sweep removed the file and the slot serves over
    `uds` again. The group teardown must then leave the socket
    directory empty."""
    import signal

    from elasticdl_tpu.common.constants import ENV_TRANSPORT, ENV_UDS_DIR
    from elasticdl_tpu.master.ps_group import PSShardGroup
    from elasticdl_tpu.rpc import transport

    def socket_files():
        return sorted(
            f for f in os.listdir(str(tmp_path)) if f.startswith("edl-uds-")
        )

    def live_socket_files():
        return sorted(
            os.path.basename(
                transport.uds_path_for(int(ep.rpartition(":")[2]))
            )
            for ep in group.endpoints
        )

    def carriers():
        return {c._transport.name for c in group.client()._clients}

    monkeypatch.delenv(ENV_TRANSPORT, raising=False)
    monkeypatch.setenv(ENV_UDS_DIR, str(tmp_path))
    group = PSShardGroup(
        2,
        mode="process",
        shard_argv=[
            "--model_zoo", FIXTURES,
            "--model_def", "linear_module.custom_model",
            "--minibatch_size", "16",
        ],
        use_async=True,
    )
    group.start()
    try:
        vec = np.arange(2048, dtype=np.float32)
        group.ensure_init(vec)
        versions, got = group.client().pull()
        np.testing.assert_array_equal(got, vec)
        assert carriers() == {"uds"}
        assert socket_files() == live_socket_files()
        dead = live_socket_files()[0]

        pid = group._procs[0].pid
        os.kill(pid, signal.SIGKILL)
        group._procs[0].wait()
        assert dead in socket_files(), "SIGKILL runs no teardown"
        group.relaunch_shard(0)  # generation 0 -> 1
        # the successor swept the dead listener's file at boot: what
        # is left is one file per live shard (the kernel may hand the
        # dead port out again, so the name alone proves nothing)
        assert socket_files() == live_socket_files()
        # the relaunched (empty) slot re-inits and serves over uds again
        group.ensure_init(vec)
        assert carriers() == {"uds"}
        for c in group.client()._clients:
            c.wire.reset()
        versions, _got = group.client().pull()
        assert len(versions) == 2
        tiers = group.client().wire_stats()["transports"]
        assert tiers["uds"]["calls"] == 2 and "grpc" not in tiers, tiers
    finally:
        group.stop()
    assert socket_files() == []


@pytest.mark.e2e
@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_stress_high_fault_rate(tmp_path, monkeypatch):
    """Long stress variant (excluded from the default tier via the
    `slow` marker): much higher fault pressure — probabilistic errors
    and latency on the whole PS plane, periodic drops, and BOTH initial
    workers crashing — must still produce exact accounting. Both
    crashes land on GetTask (between assignment and processing): a
    crash in the window between pushing a step's gradients and
    reporting the task would requeue an already-pushed task, and its
    re-run pushes again under fresh report_keys — the per-step path
    deliberately trades that re-train for liveness, so only
    assignment-window crashes keep the 16-push version invariant."""
    from elasticdl_tpu.testing import write_linear_records

    tmp = str(tmp_path)
    for i in range(2):
        write_linear_records(
            os.path.join(tmp, f"shard-{i}.rio"), 64, seed=i, noise=0.05
        )
    chaos_spec = {
        "seed": 23,
        "faults": [
            {"kind": "latency", "methods": ["PSPull", "PSPushGrad"],
             "roles": ["worker"], "prob": 0.3, "latency_ms": 15},
            {"kind": "error", "code": "UNAVAILABLE",
             "methods": ["PSPull", "PSPushGrad"], "roles": ["worker"],
             "prob": 0.15},
            {"kind": "error", "code": "DEADLINE_EXCEEDED",
             "methods": ["PSPull"], "roles": ["worker"], "nth": 1},
            {"kind": "drop", "methods": ["PSPushGrad"], "roles": ["worker"],
             "every": 7},
            {"kind": "crash", "methods": ["GetTask"], "roles": ["worker"],
             "targets": ["0"], "nth": 2, "when": "after",
             "once_file": os.path.join(tmp, "crash-0.once")},
            {"kind": "crash", "methods": ["GetTask"], "roles": ["worker"],
             "targets": ["1"], "nth": 3, "when": "before",
             "once_file": os.path.join(tmp, "crash-1.once")},
        ],
    }
    out = _run_training_job(tmp, "stress", monkeypatch, chaos_spec)
    assert out["completed_records"] == 256
    assert out["relaunches"] >= 2, "both crash faults must have fired"
    assert out["versions"] == [16, 16]
    assert out["applied"] == 32
    assert out["duplicates"] >= 1


# -- shard failover e2e (recovery plane, fault-model rung 6) -----------------


def _run_failover_job(tmp, tag, monkeypatch, chaos_spec, deepfm=False):
    """One ProcessBackend job with PROCESS-mode PS shards (plus
    process-mode KV shards for the deepfm variant) under a manually
    wired recovery plane. Mirrors _run_training_job, except shard
    deaths are real subprocess exits the plane must detect (poll_dead),
    fence, relaunch at a bumped generation, and restore."""
    from elasticdl_tpu.cluster.pod_backend import ProcessBackend
    from elasticdl_tpu.common.args import master_parser, worker_forward_args
    from elasticdl_tpu.common.constants import (
        ENV_RPC_BACKOFF,
        ENV_RPC_RETRIES,
    )
    from elasticdl_tpu.master.main import build_master
    from elasticdl_tpu.master.recovery import RecoveryPlane
    from elasticdl_tpu.master.worker_manager import WorkerManager

    if chaos_spec is None:
        monkeypatch.delenv(ENV_SPEC, raising=False)
    else:
        monkeypatch.setenv(ENV_SPEC, json.dumps(chaos_spec))
    if deepfm:
        import elasticdl_tpu.models as _models

        model_argv = [
            "--model_zoo", os.path.dirname(os.path.abspath(_models.__file__)),
            "--model_def", "deepfm_edl_embedding.custom_model",
            "--minibatch_size", "8",
            # ONE minibatch per task: every KV lookup then happens
            # BEFORE its task's only push, so a lookup outage fails the
            # task pre-push and the requeue re-runs it exactly (the
            # master-side lookup path instead rides through recovery —
            # see servicer._apply_sparse)
            "--records_per_task", "8",
            "--num_kv_shards", "2",
            "--kv_mode", "process",
        ]
    else:
        model_argv = [
            "--model_zoo", FIXTURES,
            "--model_def", "linear_module.custom_model",
            "--minibatch_size", "16",
            "--records_per_task", "16",
        ]
    args = master_parser().parse_args(
        model_argv
        + [
            "--training_data_dir", tmp,
            "--num_epochs", "2",
            "--grads_to_wait", "1",
            "--num_workers", "2",
            "--worker_backend", "process",
            "--num_ps", "2",
            "--ps_mode", "process",
            "--staleness_window", "1",
        ]
    )
    _spec, dispatcher, servicer, _evs, _ckpt = build_master(args, "training")
    unrecoverable = []
    plane = RecoveryPlane(
        servicer,
        ps_group=servicer.ps_group,
        kv_group=servicer.kv_group,
        opt_mirror_interval=0.25,
        on_unrecoverable=lambda kind, sid: unrecoverable.append((kind, sid)),
    )
    servicer.set_recovery_plane(plane)
    plane.start()
    server = RpcServer(servicer.handlers(), port=0)
    server.start()
    addr = f"localhost:{server.port}"
    log_dir = os.path.join(tmp, f"logs-{tag}")
    backend = ProcessBackend(log_dir=log_dir)
    manager = WorkerManager(
        backend,
        dispatcher,
        num_workers=2,
        worker_argv_fn=lambda wid: worker_forward_args(args, wid, addr),
        envs={
            "JAX_PLATFORMS": "cpu",
            # small retry budget: a dead shard surfaces as an outage in
            # well under a second instead of riding the production
            # backoff ladder, so workers reach _await_shard_recovery
            # while the fault is still mid-training
            ENV_RPC_RETRIES: "3",
            ENV_RPC_BACKOFF: "0.05",
        },
        max_relaunches=4,
    )
    manager.on_shard_failure = plane.on_shard_failure
    manager.start_workers()
    try:
        deadline = time.time() + 420
        while not dispatcher.finished():
            assert time.time() < deadline, f"job[{tag}] stuck"
            assert not manager.all_exited(), f"job[{tag}]: all workers gone"
            assert not unrecoverable, f"job[{tag}]: gave up on {unrecoverable}"
            time.sleep(0.05)
        assert not dispatcher.has_failed_tasks()
        versions, _vec = servicer.ps_group.assemble()
        return {
            "completed_records": dispatcher.completed_records(),
            "versions": list(versions),
            "recoveries": plane.recoveries(),
            "ps_generations": list(servicer.ps_group.generations),
            "kv_generations": (
                list(servicer.kv_group.generations)
                if servicer.kv_group is not None
                else []
            ),
            "unrecoverable": list(unrecoverable),
            "log_dir": log_dir,
        }
    finally:
        manager.on_shard_failure = None
        plane.stop()
        manager.stop_relaunch_and_remove_workers()
        backend.stop()
        server.stop()
        if servicer.kv_group is not None:
            servicer.kv_group.stop()
        if servicer.ps_group is not None:
            servicer.ps_group.stop()


@pytest.mark.e2e
@pytest.mark.chaos
def test_ps_shard_failover_exact_versions(tmp_path, monkeypatch):
    """Dense-plane failover: PS shard 1 (a real subprocess) is crashed
    server-side BEFORE applying a push, tearing the report across the
    fan-out (its pair shard may already have applied the same
    report_key). The recovery plane must fence the slot, relaunch it at
    generation 1, and restore params from a worker flat-buffer upload
    plus opt state from the master's mirror ring; the workers replay
    the torn report under its pinned key. The job must finish WITHOUT a
    master restart at final shard versions identical to a fault-free
    run — the torn push healed to exactly-once per slice."""
    from elasticdl_tpu.testing import write_linear_records

    tmp = str(tmp_path)
    for i in range(2):
        write_linear_records(
            os.path.join(tmp, f"shard-{i}.rio"), 64, seed=i, noise=0.05
        )
    chaos_spec = {
        "seed": 31,
        "faults": [
            {"kind": "crash", "methods": ["PSPushGrad"], "roles": ["ps"],
             "targets": ["1"], "side": "server", "nth": 5,
             "when": "before",
             "once_file": os.path.join(tmp, "ps-crash.once")},
        ],
    }
    under_chaos = _run_failover_job(tmp, "failover", monkeypatch, chaos_spec)
    fault_free = _run_failover_job(tmp, "clean", monkeypatch, None)

    assert os.path.exists(os.path.join(tmp, "ps-crash.once"))
    assert under_chaos["completed_records"] == 256
    assert fault_free["completed_records"] == 256
    # the slot was recovered IN PLACE at a bumped fencing generation
    assert ("ps", 1, 1) in under_chaos["recoveries"]
    assert under_chaos["ps_generations"] == [0, 1]
    assert under_chaos["unrecoverable"] == []
    # 256 records / minibatch 16 = 16 pushes per shard, exactly once
    assert under_chaos["versions"] == fault_free["versions"] == [16, 16]
    assert fault_free["recoveries"] == []


@pytest.mark.e2e
@pytest.mark.chaos
def test_shard_failover(tmp_path, monkeypatch):
    """THE recovery-plane acceptance e2e (fault-model rung 6): one job
    loses one PS shard AND one KV shard mid-training — both real
    subprocess crashes — and must recover without a master restart and
    finish with final model versions exactly equal to the fault-free
    run.

    PS shard 1 dies before a push (torn report -> pinned-key replay +
    worker-upload restore). KV shard 0 dies on a lookup: a worker-side
    lookup fails its single-minibatch task BEFORE the push (exact
    requeue), a master-side lookup rides through recovery inside
    _apply_sparse; either way the restored shard gets its rows back
    from the ring pair's mirror."""
    from elasticdl_tpu.models import deepfm_edl_embedding as dfm
    from elasticdl_tpu.models import record_codec as rc

    tmp = str(tmp_path)
    for i in range(2):
        rc.write_synthetic_tabular_records(
            os.path.join(tmp, f"shard-{i}.rio"), 32, dfm.NUM_FIELDS, 50,
            seed=i,
        )
    chaos_spec = {
        "seed": 37,
        "faults": [
            {"kind": "crash", "methods": ["PSPushGrad"], "roles": ["ps"],
             "targets": ["1"], "side": "server", "nth": 5,
             "when": "before",
             "once_file": os.path.join(tmp, "ps-crash.once")},
            {"kind": "crash", "methods": ["KVLookup"], "roles": ["kv"],
             "targets": ["0"], "side": "server", "nth": 6,
             "when": "before",
             "once_file": os.path.join(tmp, "kv-crash.once")},
        ],
    }
    under_chaos = _run_failover_job(
        tmp, "failover", monkeypatch, chaos_spec, deepfm=True
    )
    fault_free = _run_failover_job(
        tmp, "clean", monkeypatch, None, deepfm=True
    )

    assert os.path.exists(os.path.join(tmp, "ps-crash.once"))
    assert os.path.exists(os.path.join(tmp, "kv-crash.once"))
    assert under_chaos["completed_records"] == 128
    assert fault_free["completed_records"] == 128
    assert ("ps", 1, 1) in under_chaos["recoveries"]
    assert ("kv", 0, 1) in under_chaos["recoveries"]
    assert under_chaos["ps_generations"] == [0, 1]
    assert under_chaos["kv_generations"] == [1, 0]
    assert under_chaos["unrecoverable"] == []
    # 128 records / minibatch 8 = 16 pushes per dense shard, exactly
    # once — KV row values are bounded-staleness, versions are not
    assert under_chaos["versions"] == fault_free["versions"] == [16, 16]
    assert fault_free["recoveries"] == []


# -- fan-in combine under chaos, per wire codec -------------------------------


def _encode_slice(codec_name: str, dense: "np.ndarray", seed: int):
    """One worker's per-shard wire delta in the named codec. `dense`
    is the exactly-representable f32 slice the worker means to push;
    the wire form is what actually crosses (lossy for int8 forms)."""
    import ml_dtypes

    from elasticdl_tpu.common import codec

    if codec_name == "f32":
        return dense
    if codec_name == "bf16":
        # the fixture values fit bf16's mantissa exactly
        return dense.astype(ml_dtypes.bfloat16)
    if codec_name == "int8":
        return codec.quantize_int8(dense)
    # top-k forms: ship a deterministic 25% support
    rng = np.random.default_rng(seed)
    k = max(1, dense.size // 4)
    idx = np.sort(rng.choice(dense.size, size=k, replace=False))
    vals = dense[idx]
    if codec_name == "topk":
        return codec.SparseDelta(
            indices=idx.astype(np.int64), values=vals, n=dense.size
        )
    assert codec_name == "topk_int8"
    return codec.SparseDelta(
        indices=idx.astype(np.int64),
        values=codec.quantize_int8(vals),
        n=dense.size,
    )


def _fanin_chaos_job(codec_name: str, combine: bool):
    """In-process fan-in mini-job over 2 PS shard servicers: 6 worker
    threads push 8 rounds of codec-encoded window deltas, every third
    report is replayed (the drop-retry pattern — sometimes landing in
    the SAME combine batch as its original), and shard 1 fails over
    mid-job: fenced at a bumped generation, restored from its own
    state (what the recovery plane's restore does), with the torn
    report replayed under its pinned key. Returns final versions, the
    assembled model, and the dedup/combine counters."""
    import threading

    from elasticdl_tpu.master.ps_shard import (
        PSShardServicer,
        slice_boundaries,
    )
    from elasticdl_tpu.rpc.fencing import EpochFencedError

    n_params, n_workers, n_rounds = 96, 6, 8
    bounds = slice_boundaries(n_params, 2)
    shards = [
        PSShardServicer(i, 2, fanin_combine=combine, generation=0)
        for i in range(2)
    ]
    epochs = [0, 0]
    for i, (s0, s1) in enumerate(bounds):
        shards[i].init_slice(
            {"vec": np.zeros(s1 - s0, np.float32), "version": 0}
        )
    delta_unit = 2.0 ** -12  # exactly representable at any sum order

    def push_all(wid, rnd, errors=None):
        """One worker's windowed report: codec-encode each slice and
        push with a pinned report key; replay every third report."""
        rng = np.random.default_rng(1000 * wid + rnd)
        dense = (
            rng.integers(-32, 32, size=n_params) * delta_unit
        ).astype(np.float32)
        for sid, (s0, s1) in enumerate(bounds):
            wire = _encode_slice(
                codec_name, dense[s0:s1], seed=97 * wid + rnd
            )
            req = {
                "delta": wire,
                "steps": 1,
                "base_version": 0,
                "report_key": f"w{wid}:r{rnd}",
                "epoch": epochs[sid],
            }
            try:
                shards[sid].push_delta(dict(req))
                if (wid + rnd) % 3 == 0:
                    # drop-retry: the response was lost, the worker
                    # resends the SAME keyed report
                    shards[sid].push_delta(dict(req))
            except Exception as e:  # pragma: no cover - assertion surface
                if errors is not None:
                    errors.append(repr(e))
                else:
                    raise

    def failover_shard_1():
        """Tear down shard 1 mid-job and relaunch it fenced: new
        servicer at generation 1, restored from the dead shard's
        state; the report torn across the fan-out is replayed."""
        torn = {
            "steps": 1,
            "base_version": 0,
            "report_key": "torn:0",
        }
        s0, s1 = bounds[0]
        shards[0].push_delta(
            dict(
                torn,
                delta=_encode_slice(
                    codec_name,
                    np.full(s1 - s0, delta_unit, np.float32),
                    seed=7,
                ),
                epoch=epochs[0],
            )
        )
        # shard 1 "crashed" before applying its half of the report
        old = shards[1]
        state = old.pull({})
        shards[1] = PSShardServicer(
            1, 2, fanin_combine=combine, generation=1
        )
        shards[1].init_slice(
            {"vec": state["vec"], "version": state["version"]}
        )
        epochs[1] = 1
        # the stale epoch bounces off the fence (clients re-resolve)
        with pytest.raises(EpochFencedError):
            shards[1].push_delta(
                {
                    "delta": np.zeros(
                        bounds[1][1] - bounds[1][0], np.float32
                    ),
                    "steps": 1,
                    "base_version": 0,
                    "epoch": 0,
                }
            )
        # torn-report replay under the pinned key: shard 0 dedups,
        # shard 1 applies for the first time
        for sid, (s0, s1) in enumerate(bounds):
            shards[sid].push_delta(
                dict(
                    torn,
                    delta=_encode_slice(
                        codec_name,
                        np.full(s1 - s0, delta_unit, np.float32),
                        seed=7,
                    ),
                    epoch=epochs[sid],
                )
            )

    for rnd in range(n_rounds):
        if rnd == n_rounds // 2:
            failover_shard_1()
        if combine:
            errors = []
            threads = [
                threading.Thread(target=push_all, args=(w, rnd, errors))
                for w in range(n_workers)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert errors == []
        else:
            for w in range(n_workers):
                push_all(w, rnd)

    stats = [s.stats() for s in shards]
    return {
        "versions": [s["version"] for s in stats],
        "vec": np.concatenate([s.pull({})["vec"] for s in shards]),
        "duplicates": sum(s["duplicate_pushes"] for s in stats),
        "applied": sum(s["applied_pushes"] for s in stats),
        "combined_reports": sum(s["combined_reports"] for s in stats),
    }


@pytest.mark.chaos
@pytest.mark.parametrize(
    "codec_name", ["f32", "bf16", "int8", "topk", "topk_int8"]
)
def test_fanin_combine_chaos_matches_serial(codec_name):
    """The fan-in combine stage under chaos, per wire codec: replayed
    reports (drop-retry, including replays sharing a batch with their
    original) plus a mid-job fenced shard failover must land the
    combined path at EXACTLY the serial path's versions and accounting,
    with the model bit-identical for exactly-representable wire values
    (f32/bf16/topk) and trajectory-identical (same versions, same
    applies, numerically equal sums) for the lossy int8 forms."""
    combined = _fanin_chaos_job(codec_name, combine=True)
    serial = _fanin_chaos_job(codec_name, combine=False)

    # exactly-once accounting, identical on both paths: versions are
    # 6 workers x 8 rounds + the torn report = 49 per shard (the
    # restored shard RESUMES its version; its counters restart at the
    # relaunch, so applied = 49 on shard 0 + 24 post-failover rounds
    # + the torn apply = 25 on the new shard 1)
    assert combined["versions"] == serial["versions"] == [49, 49]
    assert combined["applied"] == serial["applied"] == 74
    # every replay was absorbed by the dedup ring, not double-applied:
    # (w+r)%3==0 gives 2 replays/round -> 16 on shard 0 + 8 on the
    # post-failover shard 1, plus the torn-report replay deduping on
    # the surviving shard 0
    assert combined["duplicates"] == serial["duplicates"] == 25
    # the combined run actually combined
    assert combined["combined_reports"] > 0
    assert serial["combined_reports"] == 0
    if codec_name in ("f32", "bf16", "topk"):
        np.testing.assert_array_equal(combined["vec"], serial["vec"])
    else:
        np.testing.assert_allclose(
            combined["vec"], serial["vec"], rtol=1e-6, atol=1e-7
        )


# -- flight recorder postmortem ordering --------------------------------------


@pytest.mark.e2e
@pytest.mark.chaos
def test_flight_recorder_orders_fault_fence_and_recovery():
    """The flight recorder IS the chaos postmortem: after an injected
    fault and a shard failover, the master-process ring must hold the
    whole story — chaos fault -> recovery begin -> generation bump ->
    recovery done — in causal (seq) order, because every event site
    funnels through the same lock that assigns seq."""
    from elasticdl_tpu.master.ps_group import PSShardGroup
    from elasticdl_tpu.master.recovery import RecoveryPlane
    from elasticdl_tpu.obs import flight

    from tests.fixtures import linear_module

    class _Stub:
        def shard_version_floor(self, shard_id):
            return 1 if int(shard_id) == 1 else -1

    def wait_until(predicate, timeout=15.0, what="condition"):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if predicate():
                return
            time.sleep(0.02)
        raise AssertionError(f"timed out waiting for {what}")

    flight.RECORDER.clear()
    group = PSShardGroup(
        2, mode="inproc", use_async=True,
        optimizer_factory=linear_module.optimizer,
    )
    group.start()
    try:
        n = 10
        group.ensure_init(np.arange(n, dtype=np.float32), version=0)
        client = group.client()
        versions, vec = client.push_grad(
            np.full(n, 0.5, np.float32), [0, 0], return_model=True
        )
        assert versions == [1, 1]

        # inject a retryable fault through the production interceptor
        # path — GetTrace is idempotent, so the policy rides over it
        # and the firing lands in THIS process's flight recorder
        plan = FaultPlan.from_spec(
            {
                "seed": 3,
                "faults": [
                    {"kind": "error", "code": "UNAVAILABLE",
                     "methods": ["GetTrace"], "nth": 1},
                ],
            },
            role="test",
        )
        chaotic = RpcClient(
            group.endpoints[1], policy=fast_policy(), fault_plan=plan
        )
        try:
            assert chaotic.call("GetTrace", {}, timeout=10) is not None
        finally:
            chaotic.close()

        plane = RecoveryPlane(
            _Stub(),
            ps_group=group,
            restore_deadline=20.0,
            opt_mirror_interval=0.05,
        )
        plane.start()
        try:
            wait_until(
                lambda: plane.opt_ring_depth(1) >= 1,
                what="opt mirror ring fill",
            )
            plane.on_shard_failure("ps", 1)
            wait_until(
                lambda: 1 in plane.status()["ps"], what="shard 1 fenced"
            )
            s, e = client.bounds[1]
            assert plane.offer_upload(7, 1, vec[s:e], 1) is True
            wait_until(
                lambda: ("ps", 1, 1) in plane.recoveries(),
                what="shard 1 recovery",
            )
        finally:
            plane.stop()

        events = flight.RECORDER.snapshot()
        seqs = [e["seq"] for e in events]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
        first = {}
        for ev in events:
            first.setdefault(ev["kind"], ev["seq"])
        story = ["chaos_fault", "recovery_begin", "generation_bump",
                 "recovery_done"]
        assert all(k in first for k in story), sorted(first)
        assert [first[k] for k in story] == sorted(
            first[k] for k in story
        ), {k: first[k] for k in story}
        fault = next(e for e in events if e["kind"] == "chaos_fault")
        assert fault["fault"] == "error" and fault["method"] == "GetTrace"
        bump = next(e for e in events if e["kind"] == "generation_bump")
        assert (bump["shard_kind"], bump["shard"], bump["generation"]) == (
            "ps", 1, 1,
        )
    finally:
        group.stop()
        flight.RECORDER.clear()


@pytest.mark.e2e
@pytest.mark.chaos
def test_traced_chaos_job_over_uds_emits_sync_span_tree(
    tmp_path, monkeypatch
):
    """The chaos job on the default carrier, traced
    (EDL_TRACE_SAMPLE=1) on the loop dispatch core: the master-process
    span ring must reconstruct the sync chain worker -> transport ->
    dispatcher admission -> shard apply as a Perfetto-loadable trace —
    server spans carry the uds tier and a worker-side parent (the
    envelope crossed the socket), admission waits chain under them,
    and the shard applies share their traces. Accounting stays exact: the dispatch core and the tracer
    change how requests are served and observed, never the result."""
    from elasticdl_tpu.common.constants import (
        ENV_DISPATCH,
        ENV_TRACE_SAMPLE,
        ENV_TRANSPORT,
        ENV_UDS_DIR,
    )
    from elasticdl_tpu.obs import trace as obs_trace
    from elasticdl_tpu.testing import write_linear_records

    tmp = str(tmp_path)
    for i in range(2):
        write_linear_records(
            os.path.join(tmp, f"shard-{i}.rio"), 64, seed=i, noise=0.05
        )
    monkeypatch.delenv(ENV_TRANSPORT, raising=False)
    monkeypatch.setenv(ENV_UDS_DIR, tmp)
    monkeypatch.setenv(ENV_DISPATCH, "loop")
    monkeypatch.setenv(ENV_TRACE_SAMPLE, "1")
    obs_trace.refresh()
    obs_trace.RECORDER.clear()
    chaos_spec = {
        "seed": 11,
        "faults": [
            {"kind": "error", "code": "UNAVAILABLE",
             "methods": ["PSPushGrad"], "roles": ["worker"], "every": 4,
             "max_fires": 3},
            {"kind": "drop", "methods": ["PSPushGrad"], "roles": ["worker"],
             "nth": 3},
        ],
    }
    try:
        result = _run_training_job(
            tmp, "uds-traced-chaos", monkeypatch, chaos_spec
        )
        assert result["completed_records"] == 256
        assert result["versions"] == [16, 16]
        assert result["applied"] == 32
        assert result["duplicates"] >= 1, "no drop-retry was deduped"

        spans = obs_trace.RECORDER.snapshot()
        sync = [s for s in spans if s["name"] == "rpc.server.PSPushGrad"]
        assert sync, sorted({s["name"] for s in spans})
        # the envelope crossed the socket: every sync serve names the
        # tier and chains under a worker-process client span
        assert {s["args"]["transport"] for s in sync} == {"uds"}
        assert all(s["parent_id"] for s in sync)
        sync_ids = {s["span_id"] for s in sync}
        sync_traces = {s["trace_id"] for s in sync}
        admission = [
            s for s in spans
            if s["name"] == "rpc.admission_wait"
            and s["parent_id"] in sync_ids
        ]
        assert admission, "loop-core admission waits missing"
        applies = [
            s for s in spans
            if s["name"] == "ps.apply" and s["trace_id"] in sync_traces
        ]
        assert applies, "shard applies did not join the sync traces"
        assert all(s["parent_id"] for s in applies)

        doc = obs_trace.chrome_trace_from_spans(spans)
        doc = json.loads(json.dumps(doc))  # serializable end to end
        # every span is one complete event; what else is there names
        # the row of a span that names its thread, which a call or a
        # collection that a loaded host held up does here
        # (`rpc.server.slow`, `proc.gc`)
        phases = [e["ph"] for e in doc["traceEvents"]]
        assert phases.count("X") == len(spans) > 0
        assert set(phases) <= {"X", "M"}
    finally:
        obs_trace.configure(None)
        obs_trace.RECORDER.clear()
