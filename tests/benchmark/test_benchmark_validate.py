"""The result line's validator, against the builder's contract."""

import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)

from benchmark.harness import validate  # noqa: E402

E2E = {"goodput": "samples/s/chip", "setup_s": "s"}
LAYER = {"device_idle_pct": "%", "mfu_pct": "%"}

GOOD0 = {
    "correct": True, "attempted": 400, "failed": 0,
    "metrics": {
        "goodput": {"value": 1412.4071, "unit": "samples/s/chip"},
        "setup_s": {"value": 65.3127, "unit": "s"},
    },
    "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
               "memory_peak_bytes": 13958643712},
}
GOOD1 = {
    "correct": True, "attempted": 400, "failed": 0,
    "metrics": {
        "device_idle_pct": {"value": 41.5, "unit": "%"},
        "mfu_pct": {"value": 17.25, "unit": "%"},
    },
    "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
               "memory_peak_bytes": 13958643712, "window_s": 4.0,
               "busy_s": 2.34},
    "breakdown": {"device_ops": [["fusion.1", 1.5]],
                  "idle_gaps": [["unattributed", 0.5]]},
}


def broken(base, path, value="__drop__"):
    out = copy.deepcopy(base)
    node = out
    for key in path[:-1]:
        node = node[key]
    if value == "__drop__":
        del node[path[-1]]
    else:
        node[path[-1]] = value
    return out


def test_good_lines_pass():
    assert validate.check_line(json.dumps(GOOD0), E2E, trace=False) == []
    assert validate.check_line(json.dumps(GOOD1), LAYER, trace=True) == []


def test_an_extra_top_level_key_is_ignored_as_the_driver_ignores_it():
    line = json.dumps({**GOOD0, "faults": ["x"], "notes": {"a": 1}})
    assert validate.check_line(line, E2E, trace=False) == []


@pytest.mark.parametrize(
    "base,expected,trace,path,value,word",
    [
        (GOOD0, E2E, False, ("device", "memory_peak_bytes"), "__drop__", "memory_peak_bytes"),
        (GOOD0, E2E, False, ("device", "memory_peak_bytes"), 0, "memory_peak_bytes"),
        (GOOD1, LAYER, True, ("device", "busy_s"), 0, "busy_s"),
        (GOOD1, LAYER, True, ("device", "busy_s"), 4.5, "exceeds"),
        (GOOD1, LAYER, True, ("device", "window_s"), "__drop__", "window_s"),
        (GOOD0, E2E, False, ("metrics", "goodput", "unit"), "__drop__", "goodput"),
        (GOOD0, E2E, False, ("metrics", "goodput", "unit"), "img/s", "unit"),
        (GOOD0, E2E, False, ("metrics", "goodput", "value"), float("nan"), "finite"),
        (GOOD0, E2E, False, ("metrics", "goodput", "value"), 0, "is 0"),
        (GOOD0, E2E, False, ("metrics", "setup_s"), "__drop__", "missing"),
        (GOOD0, E2E, False, ("metrics", "mfu_pct"), {"value": 1, "unit": "%"}, "not one of"),
        (GOOD1, LAYER, True, ("metrics", "mfu_pct", "value"), 120.0, "above"),
        (GOOD0, E2E, False, ("correct",), "yes", "correct"),
        (GOOD0, E2E, False, ("attempted",), "__drop__", "attempted"),
        (GOOD0, E2E, False, ("failed",), 401, "exceeds"),
        (GOOD0, E2E, False, ("device", "count"), 0, "count"),
        (GOOD0, E2E, False, ("device", "platform"), "__drop__", "platform"),
        (GOOD1, LAYER, True, ("breakdown", "device_ops"), [["op", 1.0]] * 11, "breakdown"),
        (GOOD1, LAYER, True, ("breakdown", "idle_gaps"), [["op"]], "breakdown"),
    ],
)
def test_bad_lines_are_named(base, expected, trace, path, value, word):
    line = json.dumps(broken(base, path, value))
    faults = validate.check_line(line, expected, trace=trace)
    assert faults and any(word in f for f in faults), faults


def test_an_untraced_line_needs_no_trace_keys_and_a_traced_one_does():
    assert validate.check_result(GOOD0, E2E, trace=False) == []
    assert validate.check_result(GOOD0, E2E, trace=True)


def test_not_json_and_not_one_line():
    assert validate.check_line("{not json", E2E, False)
    assert validate.check_line(json.dumps(GOOD0, indent=1), E2E, False)
    assert validate.check_line("[1, 2]", E2E, False)


def test_the_rehearsal_may_leave_device_metrics_out_and_nothing_else():
    result = broken(GOOD1, ("metrics", "mfu_pct"))
    assert validate.check_result(result, LAYER, True)
    assert validate.check_result(
        result, LAYER, True, allow_missing=("mfu_pct",)
    ) == []
