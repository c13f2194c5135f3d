"""A tiny `.xplane.pb` written by hand (protobuf wire format of
tsl/profiler/protobuf/xplane.proto), so the reduction is checked on a
trace whose answer is known to the nanosecond. `recorded.xplane.pb`
beside this file is `build()`'s output, committed.

Two device planes and a host plane, slice [1000, 11000) us:
- /device:TPU:0, line "XLA Ops": fusion.1 [0, 3000) us (clipped to
  2000 inside the slice), while.2 [4000, 8000) us holding fusion.3
  [5000, 7000) us (nested: the union counts it once), fusion.1 again
  [10000, 12000) us (clipped to 1000). Union inside the slice:
  2000 + 4000 + 1000 = 7000 us. Its lines "Steps" and "XLA Modules"
  (jit_window [0, 9000), jit_copy [9500, 12000) us) span idle time and
  must not count; the modules label a gap where no host span does.
- /device:TPU:1, line "XLA Ops": fusion.1 [2000, 5000) us: 3000 us.
- /host:CPU: the probe's slice annotation on one "python" line, and on
  another the main thread's spans get_batch [8000, 10000) us and
  PjitFunction(window) [0, 8000) us.
busy_s = (7000 + 3000) / 2 us = 0.005 s; window_s = 0.010 s.
"""

import os

SLICE = "edlbench_probe_slice"
PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "recorded.xplane.pb")


def _varint(n):
    out = bytearray()
    while True:
        byte = n & 0x7F
        n >>= 7
        out.append(byte | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(field, value):
    return _varint(field << 3) + _varint(value)


def _bytes(field, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _plane(plane_id, name, lines):
    """lines: [(line name, [(event name, start_us, end_us)])]."""
    names = sorted({e[0] for _l, events in lines for e in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    body = _int(1, plane_id) + _bytes(2, name)
    for i, (line_name, events) in enumerate(lines):
        line = _int(1, i + 1) + _bytes(2, line_name) + _int(3, 0)
        for event_name, start_us, end_us in events:
            line += _bytes(4, _int(1, ids[event_name])
                           + _int(2, start_us * 1_000_000)
                           + _int(3, (end_us - start_us) * 1_000_000))
        body += _bytes(3, line)
    for n, i in ids.items():
        body += _bytes(4, _int(1, i) + _bytes(2, _int(1, i) + _bytes(2, n)))
    return body


def build(with_devices=True, with_slice=True):
    planes = []
    if with_devices:
        planes.append(_plane(1, "/device:TPU:0", [
            ("Steps", [("step", 0, 20000)]),
            ("XLA Modules", [("jit_window(123)", 0, 9000),
                             ("jit_copy(45)", 9500, 12000)]),
            ("XLA Ops", [("fusion.1", 0, 3000), ("while.2", 4000, 8000),
                         ("fusion.3", 5000, 7000),
                         ("fusion.1", 10000, 12000)]),
        ]))
        planes.append(_plane(2, "/device:TPU:1", [
            ("XLA Ops", [("fusion.1", 2000, 5000)]),
        ]))
    host = [("python", [("get_batch", 8000, 10000),
                        ("PjitFunction(window)", 0, 8000)])]
    if with_slice:
        host.insert(0, ("python", [(SLICE, 1000, 11000)]))
    planes.append(_plane(3, "/host:CPU", host))
    return b"".join(_bytes(1, p) for p in planes)


if __name__ == "__main__":
    with open(PATH, "wb") as f:
        f.write(build())
