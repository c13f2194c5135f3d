"""Aggregation tree (elasticdl_tpu/agg/) tests: the host-local presum
rung between the workers and the PS shards.

The contract under test: routing window-delta pushes through an
aggregator node — cohort presum (`fanin.presum_f32`), ONE combined
upstream forward carrying the member report_key list, shared prepacked
fan-back — must be indistinguishable from the flat worker->PS path:
identical final model (bitwise for exactly-representable wire values,
across every codec), identical versions and dedup accounting, and
exact fallback semantics when the node dies mid-cohort (workers replay
DIRECT under the same report_key) or is fenced after a relaunch."""

import os
import signal
import threading
import time

import numpy as np
import pytest

from elasticdl_tpu.agg import aggregator as agg_mod
from elasticdl_tpu.agg.group import AggGroup
from elasticdl_tpu.common import codec
from elasticdl_tpu.common.constants import (
    ENV_AGG_BATCH,
    ENV_AGG_UPSTREAM_TIER,
    ENV_AGG_WAIT_MS,
)
from elasticdl_tpu.master.ps_group import PSShardGroup
from elasticdl_tpu.master.ps_shard import PSShardServicer
from elasticdl_tpu.rpc.ps_client import ShardedPS

# exactly representable in f32 at any summation order (same trick as
# the fan-in and chaos suites): bit-identical results regardless of
# whether members were presummed at the aggregator or applied serially
DELTA = 2.0 ** -12

N_PARAMS = 96
N_SHARDS = 2
N_WORKERS = 4
N_ROUNDS = 3


# -- env knobs ----------------------------------------------------------------


def test_agg_env_knobs():
    assert agg_mod.agg_batch({ENV_AGG_BATCH: "8"}) == 8
    assert agg_mod.agg_batch({ENV_AGG_BATCH: "junk"}) == 32
    assert agg_mod.agg_batch({ENV_AGG_BATCH: "0"}) == 1
    assert agg_mod.agg_batch({}) == 32
    assert agg_mod.agg_wait_s({ENV_AGG_WAIT_MS: "5"}) == 0.005
    assert agg_mod.agg_wait_s({ENV_AGG_WAIT_MS: "-3"}) == 0.0
    assert agg_mod.agg_wait_s({}) == 0.0
    assert agg_mod.upstream_tier({}) == "uds"
    assert agg_mod.upstream_tier({ENV_AGG_UPSTREAM_TIER: "GRPC"}) == "grpc"


# -- PS-side combined apply (ps_shard.push_delta_combined) --------------------


FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def _shard(**kw):
    kw.setdefault("fanin_combine", False)
    shard = PSShardServicer(0, 1, **kw)
    shard.init_slice({"vec": np.zeros(16, np.float32), "version": 0})
    return shard


def test_push_delta_combined_applies_once_and_registers_keys():
    shard = _shard()
    resp = shard.push_delta_combined(
        {
            "delta": np.full(16, 3 * DELTA, np.float32),
            "steps": 3,
            "report_keys": ["a", "b", "c"],
        }
    )
    assert resp["accepted"] is True and resp["version"] == 3
    np.testing.assert_array_equal(
        resp["vec"], np.full(16, 3 * DELTA, np.float32)
    )
    # every member key was registered: a direct replay (the post-crash
    # fallback path) dedups instead of double-applying
    replay = shard.push_delta(
        {
            "delta": np.full(16, DELTA, np.float32),
            "steps": 1,
            "base_version": 0,
            "report_key": "b",
        }
    )
    assert replay["duplicate"] is True
    assert shard.stats()["version"] == 3
    stats = shard.stats()
    assert stats["combined_batches"] == 1
    assert stats["combined_reports"] == 3
    assert stats["applied_pushes"] == 3


def test_push_delta_combined_rejects_replayed_member_whole():
    """All-or-nothing: a combined batch holding an already-applied key
    must apply NOTHING (the aggregator decomposes to serial forwards,
    where the shard dedups member-by-member)."""
    shard = _shard()
    shard.push_delta(
        {
            "delta": np.full(16, DELTA, np.float32),
            "steps": 1,
            "base_version": 0,
            "report_key": "seen",
        }
    )
    resp = shard.push_delta_combined(
        {
            "delta": np.full(16, 2 * DELTA, np.float32),
            "steps": 2,
            "report_keys": ["seen", "fresh"],
        }
    )
    assert resp["accepted"] is False
    assert resp["duplicates"] == ["seen"]
    assert shard.stats()["version"] == 1  # nothing from the batch landed
    # "fresh" was NOT registered by the rejected batch
    ok = shard.push_delta(
        {
            "delta": np.full(16, DELTA, np.float32),
            "steps": 1,
            "base_version": 0,
            "report_key": "fresh",
        }
    )
    assert "duplicate" not in ok or not ok.get("duplicate")
    assert shard.stats()["version"] == 2


def test_push_delta_combined_rejects_intra_batch_duplicates_and_empty():
    shard = _shard()
    dup = shard.push_delta_combined(
        {
            "delta": np.full(16, 2 * DELTA, np.float32),
            "steps": 2,
            "report_keys": ["x", "x"],
        }
    )
    assert dup["accepted"] is False
    empty = shard.push_delta_combined(
        {"delta": np.full(16, DELTA, np.float32), "steps": 1,
         "report_keys": []}
    )
    assert empty["accepted"] is False
    assert shard.stats()["version"] == 0


def test_push_delta_combined_rejects_under_staleness_window():
    """Staleness down-weighting is per-member math: the combined fast
    path must refuse and let the members go serial."""
    shard = _shard(staleness_window=2)
    resp = shard.push_delta_combined(
        {
            "delta": np.full(16, 2 * DELTA, np.float32),
            "steps": 2,
            "report_keys": ["a", "b"],
        }
    )
    assert resp["accepted"] is False
    assert shard.stats()["version"] == 0


# -- tree-vs-flat bitwise equivalence, per wire codec -------------------------


def _worker_delta(codec_name: str, wid: int, rnd: int) -> object:
    """One worker's full-vector wire delta, deterministic per (worker,
    round), exactly representable after decode in EVERY codec: int8
    forms pin the chunk max to 127*DELTA so the quantization scale is
    exactly DELTA and dequantize returns exact multiples of it."""
    rng = np.random.default_rng(1000 * wid + rnd)
    dense = (rng.integers(-126, 127, size=N_PARAMS) * DELTA).astype(
        np.float32
    )
    dense[0] = 127 * DELTA  # pin the quantization scale to DELTA
    if codec_name == "f32":
        return dense
    if codec_name == "int8":
        return codec.quantize_int8(dense)
    k = N_PARAMS // 4
    idx = np.sort(rng.choice(N_PARAMS, size=k, replace=False))
    idx[0] = 0  # keep the pinned max in the support
    idx = np.unique(idx)
    vals = dense[idx]
    if codec_name == "topk":
        return codec.SparseDelta(
            indices=idx.astype(np.int64), values=vals, n=N_PARAMS
        )
    assert codec_name == "topk_int8"
    return codec.SparseDelta(
        indices=idx.astype(np.int64),
        values=codec.quantize_int8(vals),
        n=N_PARAMS,
    )


def _run_push_rounds(codec_name: str, tree: bool, monkeypatch):
    """W workers x R rounds of keyed pushes against 2 inproc PS shards,
    either direct (flat) or through one inproc aggregator node (tree).
    Every worker holds its OWN ShardedPS — cohorts form across client
    connections, exactly as across real worker processes."""
    if tree:
        # linger so concurrent members rendezvous into one cohort
        monkeypatch.setenv(ENV_AGG_WAIT_MS, "100")
    else:
        monkeypatch.delenv(ENV_AGG_WAIT_MS, raising=False)
    group = PSShardGroup(N_SHARDS, mode="inproc")
    group.start()
    agg = None
    clients = []
    try:
        boot = ShardedPS(
            group.endpoints, N_PARAMS,
            generations=list(group.generations),
        )
        boot.init_model(np.zeros(N_PARAMS, np.float32), version=0)
        boot.close()
        if tree:
            agg = AggGroup(1, list(group.endpoints), mode="inproc")
            agg.start()
        for w in range(N_WORKERS):
            ps = ShardedPS(
                group.endpoints, N_PARAMS,
                generations=list(group.generations),
            )
            if tree:
                ps.set_aggregator(agg.endpoints[0], agg.generations[0])
            clients.append(ps)
        errors = []

        def run_worker(w):
            try:
                for rnd in range(N_ROUNDS):
                    clients[w].push_delta(
                        _worker_delta(codec_name, w, rnd),
                        1,
                        [0] * N_SHARDS,
                        report_key=f"w{w}:r{rnd}",
                    )
            except Exception as e:  # pragma: no cover - assertion surface
                errors.append(repr(e))

        threads = [
            threading.Thread(target=run_worker, args=(w,))
            for w in range(N_WORKERS)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errors == []
        versions, vec = clients[0].pull()
        shard_stats = [sv.stats() for sv in group.servicers]
        return {
            "versions": versions,
            "vec": vec,
            "applied": sum(s["applied_pushes"] for s in shard_stats),
            "duplicates": sum(s["duplicate_pushes"] for s in shard_stats),
            "combined_reports": sum(
                s["combined_reports"] for s in shard_stats
            ),
            "agg_stats": agg.servicers[0].stats() if tree else None,
        }
    finally:
        for ps in clients:
            ps.close()
        if agg is not None:
            agg.stop()
        group.stop()


@pytest.mark.parametrize("codec_name", ["f32", "int8", "topk", "topk_int8"])
def test_tree_matches_flat_bitwise(codec_name, monkeypatch):
    """The acceptance bar for the presum rung: the tree path must land
    the IDENTICAL final model (bit for bit — the fixture values are
    exactly representable in every codec) at identical versions and
    exactly-once accounting, while demonstrably combining (cohorts
    formed at the aggregator, combined batches applied at the shards)."""
    flat = _run_push_rounds(codec_name, tree=False, monkeypatch=monkeypatch)
    tree = _run_push_rounds(codec_name, tree=True, monkeypatch=monkeypatch)

    total = N_WORKERS * N_ROUNDS
    assert tree["versions"] == flat["versions"] == [total] * N_SHARDS
    assert tree["applied"] == flat["applied"] == total * N_SHARDS
    assert tree["duplicates"] == flat["duplicates"] == 0
    np.testing.assert_array_equal(tree["vec"], flat["vec"])
    # the tree actually aggregated: members entered, cohorts (or k=1
    # passthroughs) forwarded, nothing errored upstream
    st = tree["agg_stats"]
    assert st["members_in"] == total * N_SHARDS
    assert st["upstream_errors"] == 0
    assert st["cohorts_forwarded"] > 0, st
    assert tree["combined_reports"] > 0
    # the flat run never combined (no fanin stage configured)
    assert flat["combined_reports"] == 0


# -- fencing: relaunch bumps the generation -----------------------------------


def test_agg_relaunch_bumps_generation_and_fences(monkeypatch):
    """A relaunched aggregator slot must come back at a bumped fencing
    generation: pre-crash cohort members (stale epoch) bounce off the
    fence, and a worker still pointed at the dead node falls back to
    DIRECT pushes with exact versions, then re-arms at the new node."""
    from elasticdl_tpu.rpc.fencing import EpochFencedError

    monkeypatch.delenv(ENV_AGG_WAIT_MS, raising=False)
    group = PSShardGroup(N_SHARDS, mode="inproc")
    group.start()
    agg = AggGroup(1, list(group.endpoints), mode="inproc")
    agg.start()
    ps = None
    try:
        ps = ShardedPS(
            group.endpoints, N_PARAMS,
            generations=list(group.generations),
        )
        ps.init_model(np.zeros(N_PARAMS, np.float32), version=0)
        ps.set_aggregator(agg.endpoints[0], agg.generations[0])
        ps.push_delta(
            np.full(N_PARAMS, DELTA, np.float32), 1, [0] * N_SHARDS,
            report_key="pre",
        )
        assert agg.servicers[0].stats()["members_in"] == N_SHARDS

        agg.relaunch_shard(0)
        assert agg.generations[0] == 1
        # a stale-epoch member (from before the relaunch) is fenced
        with pytest.raises(EpochFencedError):
            agg.servicers[0].push_delta(
                {
                    "delta": np.zeros(1, np.float32),
                    "steps": 1,
                    "base_version": 0,
                    "report_key": "stale",
                    "shard": 0,
                    "shard_epoch": 0,
                    "epoch": 0,
                }
            )
        # the still-armed client fails against the dead endpoint, drops
        # the route, and replays DIRECT under the same report_key
        versions, _ = ps.push_delta(
            np.full(N_PARAMS, DELTA, np.float32), 1, [1] * N_SHARDS,
            report_key="during",
        )
        assert versions == [2] * N_SHARDS
        assert ps.agg_dropped is True
        # re-arm at the relaunched node: pushes flow through it again
        ps.set_aggregator(agg.endpoints[0], agg.generations[0])
        assert ps.agg_dropped is False
        versions, _ = ps.push_delta(
            np.full(N_PARAMS, DELTA, np.float32), 1, [2] * N_SHARDS,
            report_key="post",
        )
        assert versions == [3] * N_SHARDS
        assert agg.servicers[0].stats()["members_in"] == N_SHARDS
        _vers, vec = ps.pull()
        np.testing.assert_array_equal(
            vec, np.full(N_PARAMS, 3 * DELTA, np.float32)
        )
    finally:
        if ps is not None:
            ps.close()
        agg.stop()
        group.stop()


# -- upstream re-point after a PS relaunch ------------------------------------


def test_agg_update_upstream_repoints_forwards(monkeypatch):
    monkeypatch.delenv(ENV_AGG_WAIT_MS, raising=False)
    group_a = PSShardGroup(1, mode="inproc")
    group_a.start()
    group_b = PSShardGroup(1, mode="inproc")
    group_b.start()
    agg = AggGroup(1, list(group_a.endpoints), mode="inproc")
    agg.start()
    ps = None
    try:
        for g in (group_a, group_b):
            boot = ShardedPS(g.endpoints, N_PARAMS)
            boot.init_model(np.zeros(N_PARAMS, np.float32), version=0)
            boot.close()
        ps = ShardedPS(group_a.endpoints, N_PARAMS)
        ps.set_aggregator(agg.endpoints[0], agg.generations[0])
        ps.push_delta(
            np.full(N_PARAMS, DELTA, np.float32), 1, [0], report_key="a"
        )
        assert group_a.servicers[0].stats()["applied_pushes"] == 1
        # re-point the tree at the B endpoints: subsequent forwards land
        # there even though the pushing client never re-resolved
        agg.update_upstream(list(group_b.endpoints))
        ps.push_delta(
            np.full(N_PARAMS, DELTA, np.float32), 1, [0], report_key="b"
        )
        assert group_a.servicers[0].stats()["applied_pushes"] == 1
        assert group_b.servicers[0].stats()["applied_pushes"] == 1
    finally:
        if ps is not None:
            ps.close()
        agg.stop()
        group_a.stop()
        group_b.stop()


# -- aggregator death mid-cohort: fallback direct, exact versions -------------


@pytest.mark.e2e
@pytest.mark.chaos
def test_agg_sigkill_mid_cohort_falls_back_exact(tmp_path, monkeypatch):
    """SIGKILL a process-mode aggregator while a lingering cohort is
    parked on it (members submitted, forward not yet fired). Every
    member's push must fail over to a DIRECT PS push under the same
    report_key — final shard versions exactly equal the push count, no
    member lost, no member double-applied — the death is visible to the
    recovery plane via poll_dead, the relaunched slot serves at a
    bumped generation, and its boot swept the dead node's socket file:
    the job leaves none behind on stop."""
    from elasticdl_tpu.common.constants import (
        ENV_RPC_BACKOFF,
        ENV_RPC_RETRIES,
        ENV_TRANSPORT,
        ENV_UDS_DIR,
    )

    monkeypatch.delenv(ENV_TRANSPORT, raising=False)
    monkeypatch.setenv(ENV_UDS_DIR, str(tmp_path))
    # the dead node must surface as an outage fast (the client replays
    # direct), not ride the production backoff ladder
    monkeypatch.setenv(ENV_RPC_RETRIES, "2")
    monkeypatch.setenv(ENV_RPC_BACKOFF, "0.05")
    # long linger: the cohort is still parked when the kill lands
    monkeypatch.setenv(ENV_AGG_WAIT_MS, "2000")
    monkeypatch.setenv(ENV_AGG_BATCH, "64")
    group = PSShardGroup(
        N_SHARDS,
        mode="process",
        shard_argv=[
            "--model_zoo", FIXTURES,
            "--model_def", "linear_module.custom_model",
            "--minibatch_size", "16",
        ],
    )
    group.start()
    agg = AggGroup(1, list(group.endpoints), mode="process")
    agg.start()
    clients = []
    try:
        boot = ShardedPS(
            group.endpoints, N_PARAMS,
            generations=list(group.generations),
        )
        boot.init_model(np.zeros(N_PARAMS, np.float32), version=0)
        boot.close()
        for w in range(N_WORKERS):
            ps = ShardedPS(
                group.endpoints, N_PARAMS,
                generations=list(group.generations),
            )
            ps.set_aggregator(agg.endpoints[0], agg.generations[0])
            clients.append(ps)
        errors = []

        def push(w):
            try:
                clients[w].push_delta(
                    np.full(N_PARAMS, DELTA, np.float32), 1,
                    [0] * N_SHARDS, report_key=f"w{w}",
                )
            except Exception as e:  # pragma: no cover - assertion surface
                errors.append(repr(e))

        threads = [
            threading.Thread(target=push, args=(w,))
            for w in range(N_WORKERS)
        ]
        for t in threads:
            t.start()
        time.sleep(0.5)  # members parked in the linger window
        os.kill(agg._procs[0].pid, signal.SIGKILL)
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads), "push wedged"
        assert errors == []
        # every member replayed direct exactly once: versions equal the
        # push count and the model is the exact sum
        versions, vec = clients[0].pull()
        assert versions == [N_WORKERS] * N_SHARDS
        np.testing.assert_array_equal(
            vec, np.full(N_PARAMS, N_WORKERS * DELTA, np.float32)
        )
        assert all(ps.agg_dropped for ps in clients)
        # the death is observable the way the recovery plane polls it
        dead = agg.poll_dead()
        assert [d[0] for d in dead] == [0]
        assert dead[0][1] == -signal.SIGKILL
        # relaunch-not-restore: the slot comes back fenced and usable
        agg.relaunch_shard(0)
        assert agg.generations[0] == 1
        clients[0].set_aggregator(agg.endpoints[0], agg.generations[0])
        versions, _ = clients[0].push_delta(
            np.full(N_PARAMS, DELTA, np.float32), 1,
            [N_WORKERS] * N_SHARDS, report_key="post-relaunch",
        )
        assert versions == [N_WORKERS + 1] * N_SHARDS
    finally:
        for ps in clients:
            ps.close()
        agg.stop()
        group.stop()
    # the SIGKILLed node's socket file was reclaimed; teardown left the
    # directory clean (same contract as the PS SIGKILL chaos test)
    assert not [
        f for f in os.listdir(str(tmp_path)) if f.startswith("edl-uds-")
    ]
