"""The trace reduction on a recorded trace whose answer is known."""

import os
import sys

import pytest

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)

import xplane_fixture  # noqa: E402

from benchmark.harness import trace_reduce  # noqa: E402


@pytest.fixture(scope="module")
def reduction():
    return trace_reduce.reduce(trace_reduce.load(xplane_fixture.PATH))


def test_the_committed_trace_is_the_fixture_as_written():
    with open(xplane_fixture.PATH, "rb") as f:
        assert f.read() == xplane_fixture.build()


def test_union_of_overlapping_and_nested_events():
    assert trace_reduce.union([(4, 8), (5, 7), (0, 3), (2, 3), (8, 9)]) == [
        (0, 3), (4, 9),
    ]
    assert trace_reduce.union([(3, 3), (5, 4)]) == []


def test_events_are_clipped_to_the_slice_and_steps_do_not_count(reduction):
    by_chip = reduction["busy_s_by_chip"]
    assert by_chip["/device:TPU:0"] == pytest.approx(0.007)  # not 0.020
    assert by_chip["/device:TPU:1"] == pytest.approx(0.003)
    assert reduction["window_s"] == pytest.approx(0.010)


def test_busy_is_the_mean_over_device_planes_and_within_the_window(reduction):
    assert reduction["busy_s"] == pytest.approx(0.005)
    assert 0 < reduction["busy_s"] <= reduction["window_s"]


def test_breakdown_names_ops_and_attributes_gaps_to_host_spans(reduction):
    ops = dict(reduction["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.003)  # (2 + 1 + 3) ms / 2 chips
    assert ops["while.2"] == pytest.approx(0.002)
    gaps = dict(reduction["idle_gaps"])
    assert gaps["get_batch"] == pytest.approx(0.004)
    assert gaps["PjitFunction(window)"] == pytest.approx(0.001)
    assert len(reduction["device_ops"]) <= 10


def test_without_host_spans_a_gap_is_charged_to_the_program_run_around_it():
    modules = [("jit_window(123)", 0, 9000), ("jit_copy(45)", 9500, 12000)]
    assert trace_reduce._label([], modules, 3500) == "inside jit_window"
    assert trace_reduce._label([], modules, 9200) == "after jit_window"
    assert trace_reduce._label([], modules, 12500) == "after jit_copy"
    assert trace_reduce._label([], [], 100) == "unattributed"
    assert trace_reduce._label([("get_batch", 0, 9999)], modules, 3500) == "get_batch"


def test_no_device_plane_is_an_error_not_a_zero(tmp_path):
    path = tmp_path / "host_only.xplane.pb"
    path.write_bytes(xplane_fixture.build(with_devices=False))
    with pytest.raises(trace_reduce.TraceError, match="no plane"):
        trace_reduce.reduce(trace_reduce.load(str(path)))


def test_without_the_annotation_the_probes_own_bounds_are_the_slice(tmp_path):
    """The v5e trace has no host spans (the host tracer is off there)."""
    path = tmp_path / "no_slice.xplane.pb"
    path.write_bytes(xplane_fixture.build(with_slice=False))
    planes = trace_reduce.load(str(path))
    with pytest.raises(trace_reduce.TraceError, match="annotation"):
        trace_reduce.reduce(planes)
    with pytest.raises(trace_reduce.TraceError, match="annotation"):
        trace_reduce.reduce(planes, hint=(0.011, 0.001))
    hinted = trace_reduce.reduce(planes, hint=(0.001, 0.011))
    assert hinted["busy_s"] == pytest.approx(0.005)
    assert hinted["window_s"] == pytest.approx(0.010)


def test_operations_are_named_by_the_head_of_their_hlo_text():
    text = ("%convolution_multiply_fusion.15 = bf16[4096,4096]{1,0:T(8,128)"
            "(2,1)} fusion(bf16[4096,4096] %p), kind=kOutput")
    assert trace_reduce._short(text) == "convolution_multiply_fusion.15"
    assert trace_reduce._short("fusion.1") == "fusion.1"


def test_workers_traces_merge_chip_by_chip(reduction):
    one_chip = {
        "window_s": 0.010, "busy_s": 0.009,
        "busy_s_by_chip": {"/device:TPU:0": 0.009},
        "device_ops": [["fusion.1", 0.009]], "idle_gaps": [["x", 0.001]],
    }
    merged = trace_reduce.merge([reduction, one_chip])
    assert merged["chips_traced"] == 3
    assert merged["busy_s"] == pytest.approx((0.007 + 0.003 + 0.009) / 3)
    assert dict(merged["device_ops"])["fusion.1"] == pytest.approx(
        0.003 * 2 / 3 + 0.009 / 3
    )
    with pytest.raises(trace_reduce.TraceError):
        trace_reduce.merge([])


def test_find_xplane_takes_the_profiler_layout(tmp_path):
    run = tmp_path / "plugins" / "profile" / "2026_01_01"
    run.mkdir(parents=True)
    (run / "vm.xplane.pb").write_bytes(xplane_fixture.build())
    assert trace_reduce.find_xplane(str(tmp_path)).endswith("vm.xplane.pb")
    with pytest.raises(trace_reduce.TraceError):
        trace_reduce.find_xplane(str(tmp_path / "plugins"))
