"""Standalone chip probe behind `worker.DELTA_SLICE_BYTES` (PERF.md,
PR 45): a float32 vector the size of a window's delta, copied off the
device whole against in slices, alone and while a second process
receives the bytes over a Unix socket into kept memory.

    chiprun -- python scripts/d2h_stream_probe.py

It builds nothing of the worker's: its own `dynamic_slice` program, its
own copier thread, `sendall` on a socket with the transport's buffer
sizes, so it can be run on any commit. One JSON object on stdout (and in
`chiprun_out/d2h_stream_probe.json`): seconds a repetition, every
repetition kept.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import socket
import struct
import subprocess
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np

from elasticdl_tpu.rpc import transport

_LEN = struct.Struct("<Q")
MIB = 1 << 20


def receiver(path: str) -> None:
    """The far end: frames of `<u64 length><bytes>` received into one
    kept buffer, one byte back a frame; ends with its peer."""
    srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    srv.bind(path)
    srv.listen(1)
    conn, _ = srv.accept()
    transport._ask_socket_buffers(conn)
    kept = None
    while True:
        head = transport._recv_exact(conn, _LEN.size, eof_ok=True)
        if head is None:
            return
        (n,) = _LEN.unpack(head)
        if kept is None or kept.nbytes < n:
            kept = np.empty(n, np.uint8)
        transport._recv_fill(conn, memoryview(kept), n)
        conn.sendall(b"\x01")


class Copier:
    """Slices of a device vector on their way to the host, `ahead` of
    them asked for at a time; `landed(i)` blocks until slice i is."""

    def __init__(self, jax, vec, slice_elems: int, ahead: int):
        n = vec.shape[0]
        self.bounds = [
            (lo, min(lo + slice_elems, n)) for lo in range(0, n, slice_elems)
        ]
        self._jax, self._vec, self._ahead = jax, vec, ahead
        self._host = [None] * len(self.bounds)
        self._events = [threading.Event() for _ in self.bounds]
        self.t_first = self.t_last = 0.0
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self):
        self._thread.start()

    def _cut(self, i):
        lo, hi = self.bounds[i]
        piece = _slice_program(self._jax, hi - lo)(self._vec, lo)
        piece.copy_to_host_async()
        return piece

    def _run(self):
        queue = collections.deque()
        asked = 0
        self.t_first = time.perf_counter()
        for i in range(len(self.bounds)):
            while asked < len(self.bounds) and asked < i + self._ahead:
                queue.append(self._cut(asked))
                asked += 1
            self._host[i] = np.asarray(queue.popleft())
            self._events[i].set()
        self.t_last = time.perf_counter()

    def landed(self, i):
        self._events[i].wait()
        return self._host[i]


_programs: dict = {}


def _slice_program(jax, size: int):
    if size not in _programs:
        _programs[size] = jax.jit(
            lambda v, start: jax.lax.dynamic_slice(v, (start,), (size,))
        )
    return _programs[size]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--elements", type=int, default=469_285_248)  # LFM2's delta
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument(
        "--configs", default="16x2,32x2,64x2,64x3,128x2",
        help="slices to try, as <MiB>x<copies in flight>",
    )
    ap.add_argument("--receiver", default="")
    args = ap.parse_args()
    if args.receiver:
        receiver(args.receiver)
        return

    # the child first: it must not find the chip taken, and never asks
    path = os.path.join(tempfile.mkdtemp(), "probe.sock")
    child = subprocess.Popen([sys.executable, __file__, "--receiver", path])
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    n = args.elements
    out = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "elements": n,
        "bytes": n * 4,
        "reps": args.reps,
    }
    bump = jax.jit(lambda v: v + 1.0, donate_argnums=0)
    vec = jax.block_until_ready(jnp.arange(n, dtype=jnp.float32))

    conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    for _ in range(200):
        if os.path.exists(path):
            break
        time.sleep(0.05)
    conn.connect(path)
    out["sndbuf"], out["rcvbuf"] = transport._ask_socket_buffers(conn)

    def send(pieces, waited=None):
        """pieces: host arrays, or callables that block until theirs
        has landed; the seconds spent in those land in `waited`."""
        conn.sendall(_LEN.pack(n * 4))
        for piece in pieces:
            if callable(piece):
                t0 = time.perf_counter()
                piece = piece()
                waited[0] += time.perf_counter() - t0
            conn.sendall(memoryview(piece).cast("B"))
        assert conn.recv(1) == b"\x01"

    def fresh():
        nonlocal vec
        vec = jax.block_until_ready(bump(vec))  # nothing cached on the host
        return vec

    configs = [
        tuple(int(x) for x in c.split("x")) for c in args.configs.split(",")
    ]
    # warm every program and the receiver's kept buffer
    for mib, ahead in configs:
        c = Copier(jax, fresh(), mib * MIB // 4, ahead)
        c.start()
        c.landed(len(c.bounds) - 1)
    whole_host = jax.device_get(fresh())
    send([whole_host])

    def timed(fn):
        t0 = time.perf_counter()
        extra = fn()
        return time.perf_counter() - t0, extra

    # 1. the copy alone: whole, then in slices
    out["copy_whole_s"] = []
    for _ in range(args.reps):
        v = fresh()
        out["copy_whole_s"].append(timed(lambda: jax.device_get(v))[0])
    out["copy_sliced_s"] = {}
    for mib, ahead in configs:
        runs = []
        for _ in range(args.reps):
            c = Copier(jax, fresh(), mib * MIB // 4, ahead)
            c.start()
            c.landed(len(c.bounds) - 1)
            runs.append(c.t_last - c.t_first)
        out["copy_sliced_s"][f"{mib}MiB_x{ahead}"] = runs

    # 2. the send alone, from memory that has landed: one part, then 64 MiB parts
    out["send_whole_s"] = [
        timed(lambda: send([whole_host]))[0] for _ in range(args.reps)
    ]
    parts = [whole_host[lo:lo + 16 * MIB] for lo in range(0, n, 16 * MIB)]
    out["send_64MiB_parts_s"] = [
        timed(lambda: send(parts))[0] for _ in range(args.reps)
    ]

    # 3. today's order: the whole copy, then the whole send
    out["serial_s"] = []
    for _ in range(args.reps):
        v = fresh()
        out["serial_s"].append(timed(lambda: send([jax.device_get(v)]))[0])

    # 4. streamed: each slice to the socket as it lands
    out["streamed"] = {}
    for mib, ahead in configs:
        runs = []
        for _ in range(args.reps):
            c = Copier(jax, fresh(), mib * MIB // 4, ahead)
            waited = [0.0]
            t0 = time.perf_counter()
            c.start()
            send(
                [(lambda i=i: c.landed(i)) for i in range(len(c.bounds))],
                waited,
            )
            total = time.perf_counter() - t0
            runs.append(
                {
                    "total_s": total,
                    "copy_s": c.t_last - c.t_first,
                    "waited_s": waited[0],
                    "send_busy_s": total - waited[0],
                }
            )
        out["streamed"][f"{mib}MiB_x{ahead}"] = runs

    conn.close()
    child.wait(timeout=30)
    text = json.dumps(out, indent=1)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/d2h_stream_probe.json", "w") as f:
        f.write(text)
    print(text)


if __name__ == "__main__":
    main()
