"""The last line a run prints, checked against the builder's contract.

One JSON object with `correct`, `attempted`, `failed`, `metrics` and
`device` (other keys are ignored by the driver, `breakdown` is checked
when present). `metrics` gives each metric of the cell — its
end-to-end metrics in a `--trace 0` run, its per-layer metrics in a
`--trace 1` run — as `{"value", "unit"}`; `device` gives `platform`,
`kind`, `count`, `memory_peak_bytes` and, in a traced run, `window_s`
and `0 < busy_s <= window_s`. `run.py` passes its own line through
`check_line` before printing it, and the tests hold it to the same.
"""

import json
import math

TOP_KEYS = ("correct", "attempted", "failed", "metrics", "device")
BREAKDOWN_LISTS = ("device_ops", "idle_gaps")
MAX_BREAKDOWN = 10
SHARE_CEILING = 105.0  # a roofline or mfu share above this is a miscount


def _number(x):
    return (
        isinstance(x, (int, float))
        and not isinstance(x, bool)
        and math.isfinite(x)
    )


def _count(x):
    return isinstance(x, int) and not isinstance(x, bool) and x >= 0


def check_result(result, expected, trace, allow_missing=()):
    """-> list of faults (empty when `result` meets the contract).

    `expected` is {metric name: unit} for this cell and this kind of
    run; every one has to be there unless named in `allow_missing`
    (the CPU rehearsal leaves device metrics out), and no other may."""
    if not isinstance(result, dict):
        return ["the result is not a JSON object"]
    faults = [f"key {k!r} is missing" for k in TOP_KEYS if k not in result]
    if faults:
        return faults
    if not isinstance(result["correct"], bool):
        faults.append("correct is not true or false")
    for key in ("attempted", "failed"):
        if not _count(result[key]):
            faults.append(f"{key} is not a whole number >= 0")
    if not faults and result["failed"] > result["attempted"]:
        faults.append("failed exceeds attempted")

    metrics = result["metrics"]
    if not isinstance(metrics, dict):
        faults.append("metrics is not an object")
        metrics = {}
    for name, unit in expected.items():
        if name not in metrics:
            if name not in allow_missing:
                faults.append(f"metric {name!r} is missing")
            continue
        m = metrics[name]
        if not isinstance(m, dict) or "value" not in m or "unit" not in m:
            faults.append(f"metric {name!r} is not {{value, unit}}")
            continue
        if not _number(m["value"]):
            faults.append(f"metric {name!r} has no finite number as value")
        elif not trace and m["value"] == 0:
            faults.append(f"end-to-end metric {name!r} is 0")
        elif (
            name.endswith("_roofline") or "mfu" in name
        ) and m["value"] > SHARE_CEILING:
            faults.append(f"share {name!r} reads above {SHARE_CEILING} %")
        if m["unit"] != unit:
            faults.append(
                f"metric {name!r} has unit {m['unit']!r}, want {unit!r}"
            )
    for name in metrics:
        if name not in expected:
            faults.append(f"metric {name!r} is not one of this run's")

    device = result["device"]
    if not isinstance(device, dict):
        return faults + ["device is not an object"]
    for key in ("platform", "kind"):
        if not isinstance(device.get(key), str) or not device.get(key):
            faults.append(f"device.{key} is missing")
    if not _count(device.get("count")) or device.get("count") < 1:
        faults.append("device.count is not a whole number >= 1")
    peak = device.get("memory_peak_bytes")
    if not _count(peak) or peak <= 0:
        faults.append("device.memory_peak_bytes is not a whole number > 0")
    if trace:
        window, busy = device.get("window_s"), device.get("busy_s")
        if not _number(window) or window <= 0:
            faults.append("device.window_s is not a number > 0")
        if not _number(busy) or busy <= 0:
            faults.append("device.busy_s is not a number > 0")
        elif _number(window) and busy > window:
            faults.append("device.busy_s exceeds device.window_s")

    if "breakdown" in result:
        breakdown = result["breakdown"]
        if not isinstance(breakdown, dict):
            faults.append("breakdown is not an object")
            breakdown = {}
        for key in BREAKDOWN_LISTS:
            rows = breakdown.get(key, [])
            if not isinstance(rows, list) or len(rows) > MAX_BREAKDOWN:
                faults.append(f"breakdown.{key} is not a list of <= 10")
                continue
            for row in rows:
                if not (
                    isinstance(row, (list, tuple))
                    and len(row) == 2
                    and isinstance(row[0], str)
                    and _number(row[1])
                ):
                    faults.append(f"breakdown.{key} has a row {row!r}")
                    break
    return faults


def check_line(line, expected, trace, allow_missing=()):
    """`check_result` of a printed line: one line, one JSON object."""
    if "\n" in line.strip():
        return ["the result spans several lines"]
    try:
        result = json.loads(line)
    except ValueError as e:
        return [f"the line is not JSON: {e}"]
    return check_result(result, expected, trace, allow_missing)
