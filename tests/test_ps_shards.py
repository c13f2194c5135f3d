"""Sharded parameter server (master/ps_shard.py, rpc/ps_client.py).

The contract under test: splitting the flat model across N shard
endpoints must preserve the training math — a single worker in window
(local-update) mode or async per-step mode produces the SAME final
model as against the single master PS — while versions, checkpoints
and the eval cadence keep working through the master's control plane.
"""

import os

import numpy as np
import pytest

from elasticdl_tpu.common.timing import PhaseTimers
from elasticdl_tpu.api.model_spec_helpers import spec_from_module
from elasticdl_tpu.common import codec
from elasticdl_tpu.master.ps_group import PSShardGroup
from elasticdl_tpu.master.ps_shard import PSShardServicer, slice_boundaries
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.testing import InProcessMaster, build_job, write_linear_records
from elasticdl_tpu.worker.worker import Worker

from tests.fixtures import linear_module

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def test_slice_boundaries_cover_and_partition():
    for n, k in [(10, 3), (7, 7), (5, 8), (1000003, 4), (0, 2)]:
        bounds = slice_boundaries(n, k)
        assert len(bounds) == k
        assert bounds[0][0] == 0 and bounds[-1][1] == n
        for (s0, e0), (s1, e1) in zip(bounds, bounds[1:]):
            assert e0 == s1  # contiguous, no gaps/overlap
        assert sum(e - s for s, e in bounds) == n
    with pytest.raises(ValueError):
        slice_boundaries(10, 0)


def test_shard_servicer_delta_and_pull():
    shard = PSShardServicer(0, 1)
    vec = np.arange(8, dtype=np.float32)
    resp = shard.init_slice({"vec": vec, "version": 3})
    assert resp["version"] == 3
    # SETNX: second init is a no-op
    shard.init_slice({"vec": np.zeros(8, np.float32), "version": 9})
    got = shard.pull({})
    assert got["version"] == 3
    np.testing.assert_array_equal(got["vec"], vec)

    resp = shard.push_delta(
        {"delta": np.ones(8, np.float32), "steps": 4, "base_version": 3}
    )
    assert resp["version"] == 7
    assert "vec" not in resp  # base + steps == version: no merge needed
    # a pusher whose base fell behind gets the merged slice back
    resp = shard.push_delta(
        {"delta": np.ones(8, np.float32), "steps": 2, "base_version": 3}
    )
    assert resp["version"] == 9
    np.testing.assert_array_equal(resp["vec"], vec + 2.0)
    # only_if_newer honors the version
    assert shard.pull({"only_if_newer": True, "version": 9})["vec"] is None


def test_shard_servicer_async_grad_applies_immediately():
    shard = PSShardServicer(0, 1, use_async=True)  # no optimizer: plain SGD
    shard.init_slice({"vec": np.zeros(4, np.float32), "version": 0})
    resp = shard.push_grad(
        {"grad": np.full(4, 0.5, np.float32), "version": 0, "return_model": True}
    )
    assert resp["version"] == 1
    np.testing.assert_allclose(resp["vec"], -0.5)


def _run_window_job(tmp_path, tag, ps_group=None, local_updates=4, epochs=4):
    path = str(tmp_path / f"{tag}.rio")
    write_linear_records(path, 64, noise=0.05)
    # pinned shuffle: both runs must see the SAME task order for the
    # math-equivalence comparison to be meaningful
    dispatcher = TaskDispatcher({path: 64}, {}, {}, 16, epochs, shuffle_seed=7)
    spec = spec_from_module(linear_module)
    servicer, _evs, _ckpt = build_job(spec, dispatcher, grads_to_wait=1)
    if ps_group is not None:
        servicer._ps_group = servicer.ps_group = ps_group
    master = InProcessMaster(servicer)
    worker = Worker(
        0,
        master,
        spec,
        minibatch_size=16,
        local_updates=local_updates,
        ps_endpoints=ps_group.endpoints if ps_group else None,
    )
    assert worker.run()
    worker.close()
    assert dispatcher.finished()
    params, _aux, version = servicer.get_params_copy()
    return codec.ravel_np(params), version


def test_window_mode_sharded_matches_single_ps(tmp_path):
    """3 shards, one worker, SSP windows: identical math to single PS."""
    ref_vec, ref_version = _run_window_job(tmp_path, "single")
    group = PSShardGroup(
        3, mode="inproc", optimizer_factory=linear_module.optimizer
    )
    group.start()
    try:
        vec, version = _run_window_job(tmp_path, "sharded", ps_group=group)
        np.testing.assert_allclose(vec, ref_vec, rtol=0, atol=1e-6)
        assert version == ref_version
        # all shards agree on the step count at quiescence
        versions, _ = group.assemble()
        assert min(versions) == max(versions) == version
    finally:
        group.stop()


def test_async_per_step_sharded_matches_single_ps(tmp_path):
    """Async per-step gradients through 2 shards == single async PS."""

    def run(ps_group):
        path = str(tmp_path / f"async-{bool(ps_group)}.rio")
        write_linear_records(path, 64, noise=0.05)
        dispatcher = TaskDispatcher({path: 64}, {}, {}, 16, 2, shuffle_seed=7)
        spec = spec_from_module(linear_module)
        servicer, _evs, _ckpt = build_job(
            spec, dispatcher, grads_to_wait=1, use_async=True
        )
        if ps_group is not None:
            servicer._ps_group = servicer.ps_group = ps_group
        worker = Worker(
            0,
            InProcessMaster(servicer),
            spec,
            minibatch_size=16,
            ps_endpoints=ps_group.endpoints if ps_group else None,
        )
        assert worker.run()
        worker.close()
        assert dispatcher.finished()
        params, _aux, _v = servicer.get_params_copy()
        return codec.ravel_np(params)

    ref = run(None)
    group = PSShardGroup(
        2,
        mode="inproc",
        optimizer_factory=linear_module.optimizer,
        use_async=True,
    )
    group.start()
    try:
        vec = run(group)
        np.testing.assert_allclose(vec, ref, rtol=0, atol=1e-6)
    finally:
        group.stop()


def test_two_workers_sharded_window(tmp_path):
    """Concurrent workers over sharded PS: job completes, shards agree
    on the total step count, the model converges toward y=2x+1."""
    import threading

    path = str(tmp_path / "two.rio")
    write_linear_records(path, 128, noise=0.05)
    dispatcher = TaskDispatcher({path: 128}, {}, {}, 16, 4)
    spec = spec_from_module(linear_module)
    servicer, _evs, _ckpt = build_job(spec, dispatcher, grads_to_wait=1)
    # staleness window: two workers pushing summed deltas from the same
    # base overshoot at this fixture's lr; down-weighting the late
    # delta (the framework's own remedy) stabilizes the merge
    group = PSShardGroup(
        3,
        mode="inproc",
        optimizer_factory=linear_module.optimizer,
        staleness_window=1,
    )
    group.start()
    try:
        servicer._ps_group = servicer.ps_group = group
        master = InProcessMaster(servicer)
        workers = [
            Worker(
                i,
                master,
                spec_from_module(linear_module),
                minibatch_size=16,
                local_updates=2,
                ps_endpoints=group.endpoints,
            )
            for i in range(2)
        ]
        threads = [threading.Thread(target=w.run) for w in workers]
        for t in threads:
            t.start()
        for t in threads:
            t.join(180)
        for w in workers:
            w.close()
        assert dispatcher.finished()
        versions, vec = group.assemble()
        assert min(versions) == max(versions) > 0
        params = codec.unravel_np(vec, servicer.get_params_copy()[0])
        kernel = np.asarray(params["Dense_0"]["kernel"]).ravel()[0]
        assert abs(kernel - 2.0) < 0.5
    finally:
        group.stop()


def test_late_joiner_stale_windows_do_not_drag(tmp_path, monkeypatch):
    """The preemption-recovery regime (the round-4 flake, root-caused):
    a worker that pulled the model at v0 but lands its windows tens of
    versions later must not drag the converged model. The protocol
    guarantee under test is worker-side honesty — `base_version` names
    the model a delta was actually computed FROM, so versions are
    adopted only when the merged model is absorbed into the local
    trajectory, never at response time. Without that, every window
    spawned before the absorb claimed staleness 0 and the shards'
    staleness_window down-weighting never fired.

    Determinism: worker B's first pull is forced to the v0 snapshot
    (the late-joiner premise), push responses are delayed so B's whole
    stale window chain is in flight before any absorb, and B's sync
    depth is raised so backpressure doesn't serialize the chain."""
    import threading
    import time as _time

    from elasticdl_tpu.rpc.ps_client import ShardedPS

    monkeypatch.setenv("EDL_SYNC_DEPTH", "8")
    path = str(tmp_path / "late.rio")
    write_linear_records(path, 128, noise=0.05)
    spec = spec_from_module(linear_module)
    group = PSShardGroup(
        3,
        mode="inproc",
        optimizer_factory=linear_module.optimizer,
        staleness_window=1,
    )
    group.start()
    try:
        # pin the v0 snapshot the late joiner will claim as its base
        vec0 = codec.ravel_np(
            spec.model.init(
                __import__("jax").random.PRNGKey(123),
                np.zeros((1, 1), np.float32),
            )["params"]
        ).astype(np.float32)
        group.ensure_init(vec0, version=0)

        # phase 1: worker A alone converges the model (kernel -> 2)
        dispatcher_a = TaskDispatcher({path: 128}, {}, {}, 16, 4)
        servicer_a, _e, _c = build_job(spec, dispatcher_a, grads_to_wait=1)
        servicer_a._ps_group = servicer_a.ps_group = group
        worker_a = Worker(
            0,
            InProcessMaster(servicer_a),
            spec,
            minibatch_size=16,
            local_updates=2,
            ps_endpoints=group.endpoints,
        )
        assert worker_a.run()
        worker_a.close()
        versions, vec = group.assemble()
        v_converged = min(versions)
        assert v_converged >= 16  # the joiner really is tens behind
        kernel = codec.unravel_np(vec, servicer_a.get_params_copy()[0])
        k_a = np.asarray(kernel["Dense_0"]["kernel"]).ravel()[0]
        assert abs(k_a - 2.0) < 0.5

        # phase 2: worker B re-joins believing the model is at v0
        dispatcher_b = TaskDispatcher({path: 128}, {}, {}, 16, 2)
        servicer_b, _e2, _c2 = build_job(spec, dispatcher_b, grads_to_wait=1)
        servicer_b._ps_group = servicer_b.ps_group = group
        worker_b = Worker(
            1,
            InProcessMaster(servicer_b),
            spec_from_module(linear_module),
            minibatch_size=16,
            local_updates=2,
            ps_endpoints=group.endpoints,
        )
        ps = ShardedPS(group.endpoints, int(vec0.size))
        stale_pull = {"pending": True}
        orig_pull, orig_push = ps.pull, ps.push_delta

        def pull(**kwargs):
            if stale_pull["pending"]:
                stale_pull["pending"] = False
                return [0] * 3, vec0.copy()
            return orig_pull(**kwargs)

        def push_delta(*args, **kwargs):
            _time.sleep(0.3)  # keep B's whole stale chain in flight
            return orig_push(*args, **kwargs)

        ps.pull, ps.push_delta = pull, push_delta
        worker_b._ps = ps
        assert worker_b.run()
        worker_b.close()
        assert dispatcher_b.finished()

        _versions, vec_final = group.assemble()
        params = codec.unravel_np(vec_final, servicer_b.get_params_copy()[0])
        k_final = np.asarray(params["Dense_0"]["kernel"]).ravel()[0]
        # the joiner's stale windows must be staleness-weighted to
        # noise, not dumped at full weight (pre-fix this lands ~2x off)
        assert abs(k_final - 2.0) < 0.5, (
            f"late joiner dragged kernel to {k_final} (A left it at {k_a})"
        )
    finally:
        group.stop()


def test_sharded_checkpoint_cadence_via_window_meta(tmp_path):
    """ReportWindowMeta drives the checkpoint service in sharded mode
    the way version bumps do on the single PS."""
    path = str(tmp_path / "ckpt.rio")
    write_linear_records(path, 64, noise=0.05)
    dispatcher = TaskDispatcher({path: 64}, {}, {}, 16, 4)
    spec = spec_from_module(linear_module)
    ckpt_dir = str(tmp_path / "ckpts")
    servicer, _evs, ckpt = build_job(
        spec,
        dispatcher,
        grads_to_wait=1,
        checkpoint_dir=ckpt_dir,
        checkpoint_steps=4,
    )
    group = PSShardGroup(
        2, mode="inproc", optimizer_factory=linear_module.optimizer
    )
    group.start()
    try:
        servicer._ps_group = servicer.ps_group = group
        worker = Worker(
            0,
            InProcessMaster(servicer),
            spec,
            minibatch_size=16,
            local_updates=2,
            ps_endpoints=group.endpoints,
        )
        assert worker.run()
        worker.close()
        ckpt.flush()  # saves ride the async writer
        saved = [f for f in os.listdir(ckpt_dir) if f.endswith(".ckpt")]
        assert saved, "cadence crossings must produce checkpoints"
        assert servicer.version > 0  # the mirror advanced via meta
    finally:
        group.stop()


def test_sharded_eval_service_pins_and_completes(tmp_path):
    """Evaluation composes with the sharded PS: the step-based trigger
    fires off ReportWindowMeta version bumps, the eval snapshot is
    ASSEMBLED from the shards (get_params_copy), eval tasks run at the
    pinned version, and metrics land."""
    path = str(tmp_path / "ev.rio")
    write_linear_records(path, 64, noise=0.05)
    eval_path = str(tmp_path / "ev-eval.rio")
    write_linear_records(eval_path, 32, seed=1, noise=0.05)
    dispatcher = TaskDispatcher({path: 64}, {eval_path: 32}, {}, 16, 4)
    spec = spec_from_module(linear_module)
    servicer, eval_service, _ckpt = build_job(
        spec, dispatcher, grads_to_wait=1, eval_steps=4
    )
    metrics_seen = []
    eval_service._metrics_writer = lambda version, metrics: metrics_seen.append(
        (version, dict(metrics))
    )
    group = PSShardGroup(
        2, mode="inproc", optimizer_factory=linear_module.optimizer
    )
    group.start()
    try:
        servicer._ps_group = servicer.ps_group = group
        worker = Worker(
            0,
            InProcessMaster(servicer),
            spec,
            minibatch_size=16,
            local_updates=2,
            ps_endpoints=group.endpoints,
        )
        assert worker.run()
        worker.close()
        assert dispatcher.finished()
        assert metrics_seen, "eval jobs must produce metrics"
        for _version, metrics in metrics_seen:
            assert "mse" in metrics and np.isfinite(metrics["mse"])
    finally:
        group.stop()


def test_transient_shard_failure_push_retries_untorn():
    """VERDICT r4 #9: a shard endpoint blipping mid-push (UNAVAILABLE)
    must not tear the report. Two transient shapes, now injected at the
    gRPC interceptor layer (rpc/chaos.py) so the REAL retry path —
    RpcClient.call under the shared RetryPolicy — is what recovers:
    (a) `error`: the request never reached the shard — the retry
    applies it; (b) `drop`: the shard APPLIED it but the response was
    lost — the retry hits the shard's report_key dedup and must NOT
    double-apply."""
    from elasticdl_tpu.rpc.chaos import FaultPlan
    from elasticdl_tpu.rpc.client import RpcClient
    from elasticdl_tpu.rpc.policy import RetryPolicy
    from elasticdl_tpu.rpc.ps_client import ShardedPS

    fast = RetryPolicy(initial_backoff=0.01, max_backoff=0.05)

    def blip_shard_1(ps, group, kind):
        """Swap shard 1's client for one whose first PSPushDelta blips."""
        ps._clients[1].close()
        ps._clients[1] = RpcClient(
            group.endpoints[1],
            policy=fast,
            fault_plan=FaultPlan.from_spec(
                {"faults": [{"kind": kind, "methods": ["PSPushDelta"],
                             "nth": 1}]}
            ),
        )

    group = PSShardGroup(3, mode="inproc")
    group.start()
    try:
        vec0 = np.zeros(10, np.float32)
        group.ensure_init(vec0, version=0)
        ps = ShardedPS(group.endpoints, 10)

        # (a) lost request: shard 1's first PSPushDelta errors pre-send
        blip_shard_1(ps, group, "error")
        versions, _ = ps.push_delta(
            np.ones(10, np.float32), steps=2, base_versions=[0, 0, 0]
        )
        assert versions == [2, 2, 2], f"torn after lost request: {versions}"
        _, vec = ps.pull()
        np.testing.assert_allclose(vec, 1.0)
        assert group.servicers[1].stats()["duplicate_pushes"] == 0

        # (b) applied-but-response-lost: the dedup must absorb the retry
        blip_shard_1(ps, group, "drop")
        versions, _ = ps.push_delta(
            np.ones(10, np.float32), steps=2, base_versions=[2, 2, 2]
        )
        assert versions == [4, 4, 4], f"torn after response loss: {versions}"
        _, vec = ps.pull()
        np.testing.assert_allclose(vec, 2.0)  # applied exactly once
        assert group.servicers[1].stats()["duplicate_pushes"] == 1
        ps.close()
    finally:
        group.stop()


def test_master_refuses_direct_gradients_in_sharded_mode(tmp_path):
    spec = spec_from_module(linear_module)
    servicer, _evs, _ckpt = build_job(spec, None, grads_to_wait=1)
    group = PSShardGroup(2, mode="inproc")
    group.start()
    try:
        servicer._ps_group = servicer.ps_group = group
        with pytest.raises(ValueError, match="shard endpoints"):
            servicer.report_gradient({"version": 0, "gradient": None})
        with pytest.raises(ValueError, match="shard endpoints"):
            servicer.report_local_update(
                {"steps": 1, "base_version": 0, "delta_flat": np.zeros(2)}
            )
    finally:
        group.stop()


def test_validate_ps_args_rejects_strict_sync():
    from argparse import Namespace

    from elasticdl_tpu.common.args import validate_ps_args

    bad = Namespace(
        num_ps=2, use_async=False, local_updates=0, staleness_window=0
    )
    with pytest.raises(ValueError, match="strict per-step sync"):
        validate_ps_args(bad)
    for ok in (
        Namespace(num_ps=0, use_async=False, local_updates=0, staleness_window=0),
        Namespace(num_ps=2, use_async=True, local_updates=0, staleness_window=0),
        Namespace(num_ps=2, use_async=False, local_updates=8, staleness_window=0),
        Namespace(num_ps=2, use_async=False, local_updates=0, staleness_window=4),
    ):
        validate_ps_args(ok)


def test_k8s_mode_shard_group_uses_pod_backend():
    """worker_backend=k8s + num_ps: shards become dedicated pods
    addressed by pod IP (localhost subprocesses would be unreachable
    from worker pods). Driven against a fake backend, matching the
    repo's k8s test pattern."""

    class FakeK8s:
        def __init__(self):
            self.started = []
            self.deleted = []

        def start_ps_shard(self, shard_id, argv, port=2223):
            self.started.append((shard_id, list(argv)))
            return f"10.0.0.{shard_id + 1}:{port}"

        def delete_ps_shard(self, shard_id):
            self.deleted.append(shard_id)

    backend = FakeK8s()
    group = PSShardGroup(
        2,
        mode="k8s",
        shard_argv=["--model_zoo", "z", "--model_def", "m.f",
                    "--minibatch_size", "16"],
        k8s_backend=backend,
    )
    endpoints = group.start()
    assert endpoints == ["10.0.0.1:2223", "10.0.0.2:2223"]
    (i0, argv0), (i1, argv1) = backend.started
    assert (i0, i1) == (0, 1)
    assert "--shard_id" in argv0 and "--num_shards" in argv0
    group.stop()
    assert backend.deleted == [0, 1]
    with pytest.raises(ValueError, match="cluster backend"):
        PSShardGroup(2, mode="k8s", shard_argv=[])


def test_process_mode_shard_group(tmp_path):
    """Real shard subprocesses: ephemeral-port discovery, init, push,
    pull, teardown (the hosting mode the master uses for --num_ps)."""
    fixtures_dir = os.path.join(os.path.dirname(__file__), "fixtures")
    group = PSShardGroup(
        2,
        mode="process",
        shard_argv=[
            "--model_zoo", fixtures_dir,
            "--model_def", "linear_module.custom_model",
            "--minibatch_size", "16",
        ],
    )
    group.start()
    try:
        assert len(group.endpoints) == 2
        vec = np.arange(10, dtype=np.float32)
        versions = group.ensure_init(vec, version=0)
        assert versions == [0, 0]
        client = group.client()
        new_versions, merged = client.push_delta(
            np.ones(10, np.float32), steps=2, base_versions=[0, 0]
        )
        assert new_versions == [2, 2]
        assert merged == {}
        got_versions, got = client.pull()
        assert got_versions == [2, 2]
        np.testing.assert_allclose(got, vec + 1.0)
    finally:
        group.stop()


# -- the spawn contract: group and shard main are one version ------------------


class _RecordedSpawn:
    """Stands in for subprocess.Popen inside master/shard_host.py:
    keeps the argv a group would have executed and publishes a port,
    so `start()` returns without a process."""

    spawned = []

    def __init__(self, argv, env=None):
        type(self).spawned.append(list(argv))
        with open(argv[argv.index("--port_file") + 1], "w") as f:
            f.write("1")
        self.returncode = None

    def poll(self):
        return self.returncode

    def terminate(self):
        self.returncode = 0

    def wait(self, timeout=None):
        return self.returncode


def _ps_group():
    return PSShardGroup(
        2,
        mode="process",
        shard_argv=[
            "--model_zoo", FIXTURES,
            "--model_def", "linear_module.custom_model",
            "--minibatch_size", "16",
        ],
        use_async=True,
        lr_staleness_modulation=True,
        fanin_combine=True,
    )


def _kv_group():
    from elasticdl_tpu.master.kv_group import KVShardGroup

    return KVShardGroup(2, mode="process")


def _agg_group():
    from elasticdl_tpu.agg.group import AggGroup

    return AggGroup(2, ["localhost:1", "localhost:2"], mode="process")


@pytest.mark.parametrize(
    "make_group, entry, parser_name",
    [
        (_ps_group, "elasticdl_tpu.master.ps_shard_main", "ps_shard_parser"),
        (_kv_group, "elasticdl_tpu.master.kv_shard_main", "kv_shard_parser"),
        (_agg_group, "elasticdl_tpu.agg.agg_main", "agg_parser"),
    ],
    ids=["ps", "kv", "agg"],
)
def test_group_spawn_argv_parses_under_its_mains_parser(
    make_group, entry, parser_name, monkeypatch
):
    """A group and the main it spawns ship together, so a flag removed
    on one side only is a boot failure of every process-mode job.
    Everything a group hands its subprocess must parse under that
    main's OWN parser with nothing left over, and say which slot and
    which fencing generation it is — for a first boot and a relaunch."""
    import importlib

    from elasticdl_tpu.master import shard_host

    monkeypatch.setattr(_RecordedSpawn, "spawned", [])
    monkeypatch.setattr(shard_host.subprocess, "Popen", _RecordedSpawn)
    group = make_group()
    try:
        group.start()
        group.relaunch_shard(1)
    finally:
        group.stop()
    spawned = _RecordedSpawn.spawned
    assert len(spawned) == 3
    parser = getattr(importlib.import_module(entry), parser_name)()
    slots = []
    for argv in spawned:
        assert argv[1:3] == ["-m", entry]
        args, unknown = parser.parse_known_args(argv[3:])
        assert unknown == [], unknown
        slot = args.agg_id if entry.endswith("agg_main") else args.shard_id
        slots.append((slot, args.generation))
    assert slots == [(0, 0), (1, 0), (1, 1)]


# -- pull prepack cache (model-down broadcast) --------------------------------


def test_pull_prepack_one_encode_serves_fleet():
    """N pulls of one version cost ONE encode; the cached Prepacked
    frame duck-types as the response dict for direct callers."""
    shard = PSShardServicer(0, 1)
    vec = np.arange(64, dtype=np.float32)
    shard.init_slice({"vec": vec, "version": 0})
    for _ in range(8):
        got = shard.pull({})
        assert got["version"] == 0
        np.testing.assert_array_equal(got["vec"], vec)
    stats = shard.stats()
    assert stats["prepack_encodes"] == 1
    assert stats["prepack_served_pulls"] == 8
    assert stats["prepack_served_pulls"] // stats["prepack_encodes"] >= 8


def test_pull_prepack_version_bump_invalidates():
    """A push evicts the stale version's frames; the next pull encodes
    the new version once and serves it thereafter."""
    shard = PSShardServicer(0, 1)
    vec = np.arange(16, dtype=np.float32)
    shard.init_slice({"vec": vec, "version": 0})
    shard.pull({})
    shard.push_delta(
        {"delta": np.ones(16, np.float32), "steps": 1, "base_version": 0}
    )
    for _ in range(3):
        got = shard.pull({})
        assert got["version"] == 1
        np.testing.assert_array_equal(got["vec"], vec + 1.0)
    stats = shard.stats()
    assert stats["prepack_encodes"] == 2  # v0 once, v1 once
    assert stats["prepack_served_pulls"] == 4


def test_pull_prepack_caches_wire_forms_separately():
    """model_dtype selects the wire form; each (version, form) pair is
    its own cache entry, so mixed-dtype fleets don't thrash."""
    shard = PSShardServicer(0, 1)
    vec = np.arange(32, dtype=np.float32)
    shard.init_slice({"vec": vec, "version": 0})
    for _ in range(2):
        f32 = shard.pull({})
        bf16 = shard.pull({"model_dtype": "bfloat16"})
        np.testing.assert_array_equal(f32["vec"], vec)
        np.testing.assert_allclose(bf16["vec"], vec, rtol=0.01)
    stats = shard.stats()
    assert stats["prepack_encodes"] == 2
    assert stats["prepack_served_pulls"] == 4


def test_pull_encode_runs_outside_shard_lock():
    """Lock-discipline regression (the hoist this cache exists for): a
    slow pull encode must NOT serialize push appliers on the shard
    lock. A patched encoder blocks mid-encode until a concurrent
    push_delta completes; if the encode held self._lock the push could
    never finish and the flag would stay False. The version bump also
    forces the encoder's re-check loop, so the pull must come back with
    the POST-push version — the tear detection observed the mutation."""
    import threading

    from elasticdl_tpu.common import messages as messages_mod

    shard = PSShardServicer(0, 1)
    vec = np.zeros(32, np.float32)
    shard.init_slice({"vec": vec, "version": 0})

    in_encode = threading.Event()
    push_done = threading.Event()
    real_pack = messages_mod.pack
    blocked_once = []

    def slow_pack(obj):
        if not blocked_once and isinstance(obj, dict) and "vec" in obj:
            blocked_once.append(True)
            in_encode.set()
            push_done.wait(timeout=10)
        return real_pack(obj)

    result = {}

    def puller():
        result["resp"] = shard.pull({})

    messages_mod.pack = slow_pack
    try:
        t = threading.Thread(target=puller)
        t.start()
        assert in_encode.wait(timeout=10), "pull never reached the encoder"
        # the push must proceed WHILE the encode is blocked: it needs
        # self._lock, which a hoisted encode does not hold
        shard.push_delta(
            {"delta": np.ones(32, np.float32), "steps": 1, "base_version": 0}
        )
        push_done.set()
        t.join(timeout=10)
        assert not t.is_alive(), "pull deadlocked against push"
    finally:
        messages_mod.pack = real_pack
        push_done.set()
    # the re-check loop saw the bump and re-encoded the newer version
    assert result["resp"]["version"] == 1
    np.testing.assert_array_equal(result["resp"]["vec"], np.ones(32))


def test_pull_prepack_over_the_wire_one_encode_and_views_outlive_server(
    monkeypatch, tmp_path
):
    """Over the local carrier N pullers of one version are served the
    SAME cached frame bytes: one encode, no per-pull copy on the serve
    path. An array a client decoded from its reply is a view of the
    buffer the reply was received into, which the client owns: it
    stays readable after the server stops — only new calls fail."""
    from elasticdl_tpu.common.constants import ENV_TRANSPORT, ENV_UDS_DIR
    from elasticdl_tpu.rpc.client import RpcClient
    from elasticdl_tpu.rpc.server import RpcServer

    monkeypatch.delenv(ENV_TRANSPORT, raising=False)
    monkeypatch.setenv(ENV_UDS_DIR, str(tmp_path))
    shard = PSShardServicer(0, 1)
    server = RpcServer(shard.handlers(), port=0)
    shard.attach_wire_stats(server.wire)
    server.start()
    clients = [RpcClient(f"localhost:{server.port}") for _ in range(4)]
    try:
        assert {c._transport.name for c in clients} == {"uds"}
        vec = np.arange(1024, dtype=np.float32)
        clients[0].call("PSInit", {"vec": vec, "version": 0})
        got = [c.call("PSPull", {}) for c in clients]
        stats = shard.stats()
        assert stats["prepack_encodes"] == 1
        assert stats["prepack_served_pulls"] == 4
        assert stats["prepack_encode_copy_bytes"] == 0
        server.stop()
        for c in clients:
            c.close()
        # the already-decoded responses stay readable post-close
        for resp in got:
            assert resp["version"] == 0
            np.testing.assert_array_equal(resp["vec"], vec)
    finally:
        for c in clients:
            c.close()
        server.stop()


def test_reset_local_state_clears_shard_versions():
    """ADVICE r3 (high): after a failed sync the sharded pull must be
    unconditional — a surviving per-shard version vector would let
    only_if_newer return no payload and the diverged local params
    outlive the reset."""
    import threading

    w = Worker.__new__(Worker)
    w.timers = PhaseTimers()
    w._report_lock = threading.Lock()
    w._sync_epoch = 0
    w._fresh = True
    w._version = 7
    w._shard_versions = [7, 7, 7]
    w._sync_result = (1, None, None, 9, None)
    w._base_snapshots = {1: None}
    w._lineage_version = 7
    w._shard_lineage = [7, 7, 7]
    w._own_steps_abs = 4
    w._lineage_anchor_abs = 2
    w._spawn_abs = {1: 4}
    w._opt_state = object()
    w._pending_steps = 3
    w._pending_losses = [0.1]
    w._ef_lock = threading.Lock()
    w._ef_residual = object()
    w._ef_grad_residual = object()
    w._reset_local_state()
    assert w._shard_versions is None
    assert w._version == -1
    assert not w._fresh
    assert w._sync_result is None and not w._base_snapshots
    # error-feedback residuals belong to the discarded trajectory
    assert w._ef_residual is None and w._ef_grad_residual is None
