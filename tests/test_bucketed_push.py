"""The bucketed per-layer delta push (EDL_SYNC_BUCKET_BYTES, sharded PS).

Bucketed pushes cut the delta at layer-aligned bounds; adjacent
bucket slices reassemble bit-identically in EVERY wire form, the
shard parks partial sets (atomic apply), and the bucketed job lands
on the same model as the flat job to the last bit.
"""

import numpy as np
import pytest

from elasticdl_tpu.api.model_spec_helpers import spec_from_module
from elasticdl_tpu.common import codec
from elasticdl_tpu.common.constants import ENV_SYNC_BUCKET_BYTES
from elasticdl_tpu.master.ps_group import PSShardGroup
from elasticdl_tpu.master.ps_shard import PSShardServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.testing import (
    InProcessMaster,
    build_job,
    write_linear_records,
)
from elasticdl_tpu.worker.worker import Worker

from tests.fixtures import linear_module


def _dummy_worker(**kwargs):
    return Worker(
        0,
        None,
        spec_from_module(linear_module),
        minibatch_size=4,
        **kwargs,
    )


# -- knob parsing / env fallbacks --------------------------------------------


def test_sync_knob_env_fallbacks_and_validation(monkeypatch):
    monkeypatch.setenv(ENV_SYNC_BUCKET_BYTES, "4096")
    w = _dummy_worker()
    assert w._sync_bucket_bytes == 4096
    monkeypatch.delenv(ENV_SYNC_BUCKET_BYTES)
    w = _dummy_worker()
    assert w._sync_bucket_bytes == 0
    with pytest.raises(ValueError, match="sync_bucket_bytes"):
        _dummy_worker(sync_bucket_bytes=-1)


# -- bucket bounds: layer-aligned greedy packing -----------------------------


def test_bucket_bounds_layer_aligned_cover():
    w = _dummy_worker(sync_bucket_bytes=256 * 4)  # budget: 256 elems
    w._template = {
        "a": np.zeros(300, np.float32),  # oversized: split at 256
        "b": np.zeros(200, np.float32),
        "c": np.zeros(24, np.float32),
    }
    bounds = w._bucket_bounds_for(524)
    assert bounds[0] == 0 and bounds[-1] == 524
    assert all(b > a for a, b in zip(bounds, bounds[1:]))
    # the oversized leaf is cut at the budget; the small leaves are
    # NEVER split — 500 is the b/c layer boundary (300+200), not a
    # mid-leaf cut at 512
    assert bounds == [0, 256, 500, 524]
    # cached until the flat size changes
    assert w._bucket_bounds_for(524) is bounds
    # no template (pre-init): fixed-size cuts still cover exactly
    w._template = None
    w._bucket_bounds = None
    bounds = w._bucket_bounds_for(1000)
    assert bounds[0] == 0 and bounds[-1] == 1000
    assert all(b - a <= 256 for a, b in zip(bounds, bounds[1:]))


# -- bucket slicing: bit-identical reassembly in every wire form -------------


def _wire_form_deltas(n, rng):
    dense = (rng.standard_normal(n) * 1e-2).astype(np.float32)
    idx = np.sort(rng.choice(n, size=n // 3, replace=False))
    vals = dense[idx]
    return {
        "f32": dense,
        "bf16": dense.astype(codec.dtype_from_str("bfloat16")),
        "int8": codec.quantize_int8(dense, chunk=7),
        "topk": codec.SparseDelta(indices=idx, values=vals, n=n),
        "topk_int8": codec.SparseDelta(
            indices=idx,
            values=codec.quantize_int8(vals, chunk=5),
            n=n,
        ),
    }


@pytest.mark.parametrize(
    "form", ["f32", "bf16", "int8", "topk", "topk_int8"]
)
def test_adjacent_bucket_slices_reassemble_bit_identically(form):
    """The bucketed push's correctness floor: cutting a delta of ANY
    wire form at arbitrary bounds and decoding the pieces must equal
    decoding the whole — int8 scales stay in absolute chunk
    coordinates through the slice, so dequantization cannot shift."""
    rng = np.random.default_rng(3)
    n = 101
    delta = _wire_form_deltas(n, rng)[form]
    whole = codec.delta_to_f32(delta)
    bounds = [0, 13, 14, 52, 96, 101]  # deliberately chunk-misaligned
    pieces = [
        codec.delta_to_f32(codec.slice_delta(delta, a, b))
        for a, b in zip(bounds, bounds[1:])
    ]
    np.testing.assert_array_equal(np.concatenate(pieces), whole)
    assert sum(p.size for p in pieces) == n


# -- shard parking: park, atomic apply, dedup --------------------------------


def test_shard_parks_partial_set_and_applies_atomically():
    shard = PSShardServicer(0, 1)
    shard.init_slice({"vec": np.zeros(8, np.float32), "version": 0})
    d = np.arange(8, dtype=np.float32)
    common = {"steps": 2, "base_version": 0, "report_key": "w0"}
    r = shard.push_delta_bucket(
        {"delta": d[:5], "offset": 0, "bucket_index": 0,
         "num_buckets": 2, **common}
    )
    # partial set: parked, nothing applied, version unmoved
    assert r == {"version": 0, "parked": 1}
    assert shard.stats()["parked_bucket_sets"] == 1
    np.testing.assert_array_equal(shard.pull({})["vec"], np.zeros(8))
    r = shard.push_delta_bucket(
        {"delta": d[5:], "offset": 5, "bucket_index": 1,
         "num_buckets": 2, **common}
    )
    # complete set: applied atomically, version advances by steps ONCE
    assert r["version"] == 2 and "parked" not in r
    assert shard.stats()["parked_bucket_sets"] == 0
    np.testing.assert_array_equal(shard.pull({})["vec"], d)
    # a replayed part of the applied set dedups (same report_key):
    # version unmoved, the replayer gets the merged slice to rebase on
    r = shard.push_delta_bucket(
        {"delta": d[:5], "offset": 0, "bucket_index": 0,
         "num_buckets": 2, **common}
    )
    assert r["duplicate"] and r["version"] == 2
    np.testing.assert_array_equal(shard.pull({})["vec"], d)


def test_shard_bucketed_apply_matches_flat_push_bit_identically():
    d = np.linspace(-1, 1, 16).astype(np.float32)
    flat = PSShardServicer(0, 1)
    flat.init_slice({"vec": np.ones(16, np.float32), "version": 0})
    flat.push_delta({"delta": d, "steps": 3, "base_version": 0})
    bucketed = PSShardServicer(0, 1)
    bucketed.init_slice({"vec": np.ones(16, np.float32), "version": 0})
    for j, (a, b) in enumerate(zip([0, 5, 11], [5, 11, 16])):
        bucketed.push_delta_bucket(
            {"delta": d[a:b], "offset": a, "bucket_index": j,
             "num_buckets": 3, "steps": 3, "base_version": 0,
             "report_key": "w0"}
        )
    assert flat.pull({})["version"] == bucketed.pull({})["version"] == 3
    np.testing.assert_array_equal(
        flat.pull({})["vec"], bucketed.pull({})["vec"]
    )


def test_shard_re_sent_parked_part_overwrites_idempotently():
    shard = PSShardServicer(0, 1)
    shard.init_slice({"vec": np.zeros(4, np.float32), "version": 0})
    common = {"steps": 1, "base_version": 0, "report_key": "w1",
              "num_buckets": 2}
    shard.push_delta_bucket(
        {"delta": np.full(2, 9.0, np.float32), "offset": 0,
         "bucket_index": 0, **common}
    )
    # the retry re-sends bucket 0 with the REAL payload: slot
    # overwritten, not double-counted
    shard.push_delta_bucket(
        {"delta": np.ones(2, np.float32), "offset": 0,
         "bucket_index": 0, **common}
    )
    r = shard.push_delta_bucket(
        {"delta": np.ones(2, np.float32), "offset": 2,
         "bucket_index": 1, **common}
    )
    assert r["version"] == 1
    np.testing.assert_array_equal(shard.pull({})["vec"], np.ones(4))


# -- end-to-end: the bucketed job -------------------------------------------


def _run_window_job(tmp_path, tag, ps_group, **worker_kwargs):
    path = str(tmp_path / f"{tag}.rio")
    write_linear_records(path, 64, noise=0.05)
    dispatcher = TaskDispatcher(
        {path: 64}, {}, {}, 16, 4, shuffle_seed=7
    )
    spec = spec_from_module(linear_module)
    servicer, _evs, _ckpt = build_job(spec, dispatcher, grads_to_wait=1)
    servicer._ps_group = servicer.ps_group = ps_group
    worker = Worker(
        0,
        InProcessMaster(servicer),
        spec,
        minibatch_size=16,
        local_updates=4,
        ps_endpoints=ps_group.endpoints,
        **worker_kwargs,
    )
    assert worker.run()
    worker.close()
    assert dispatcher.finished()
    params, _aux, version = servicer.get_params_copy()
    return codec.ravel_np(params), version


def test_bucketed_sharded_job_matches_flat_bit_identically(tmp_path):
    """The full pipeline: worker cuts at layer-aligned bounds, shards
    park and apply atomically — the final model must equal the flat
    sharded push to the last bit, with the same version lineage."""
    group = PSShardGroup(
        3, mode="inproc", optimizer_factory=linear_module.optimizer
    )
    group.start()
    try:
        ref, ref_v = _run_window_job(tmp_path, "flat", group)
    finally:
        group.stop()
    group = PSShardGroup(
        3, mode="inproc", optimizer_factory=linear_module.optimizer
    )
    group.start()
    try:
        # budget of ONE f32 element: every parameter its own bucket —
        # the maximally-adversarial streaming shape
        vec, v = _run_window_job(
            tmp_path, "bucketed", group, sync_bucket_bytes=4
        )
        versions, _ = group.assemble()
        assert min(versions) == max(versions) == v
    finally:
        group.stop()
    assert v == ref_v
    np.testing.assert_array_equal(vec, ref)
