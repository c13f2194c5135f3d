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

    chiprun -- python scripts/d2h_stream_probe.py --case beside

is the case the serial chain rests on since PR 59 (PERF.md, PR 59): the
slices of a snapshot that is READY, cut by one program, copied out
while a second program runs for about `--busy_s` seconds (a loop of
matmuls that ends in a pass over a donated vector, as a window does).
Per repetition: the copy's seconds and GB/s and the program's seconds,
beside each other and each alone; then the same with the host's
subtraction of a kept base from every landed slice (on the copier's
thread | on a thread of its own) and the send of the differences to the
second process (`chiprun_out/d2h_beside_probe.json`).
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
    them asked for at a time; `landed(i)` blocks until slice i is.
    `pieces`: the slices already cut (a snapshot's), let go as they
    land; else each is cut from `vec` as its copy is asked for."""

    def __init__(self, jax, vec, slice_elems: int, ahead: int, pieces=None):
        n = vec.shape[0]
        self.bounds = [
            (lo, min(lo + slice_elems, n)) for lo in range(0, n, slice_elems)
        ]
        self._jax, self._vec, self._ahead = jax, vec, ahead
        self._pieces = None if pieces is None else list(pieces)
        self._host = [None] * len(self.bounds)
        self._events = [threading.Event() for _ in self.bounds]
        self.t_first = self.t_last = 0.0
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self):
        self._thread.start()

    def _cut(self, i):
        lo, hi = self.bounds[i]
        if self._pieces is not None:
            piece, self._pieces[i] = self._pieces[i], None
        else:
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


def beside(jax, jnp, args, out, send):
    """PR 59's case: a ready snapshot's slices leave the chip while a
    program runs. `send(pieces, waited)` is main's."""
    n, reps = args.elements, args.reps
    step = 128 * MIB // 4
    bounds = [(lo, min(lo + step, n)) for lo in range(0, n, step)]
    snapshot = jax.jit(lambda v: tuple(v[lo:hi] for lo, hi in bounds))

    def window(v, x, iters):  # a loop of matmuls, then the vector's pass
        def body(_, y):
            return jnp.tanh(y @ x).astype(jnp.bfloat16)

        y = jax.lax.fori_loop(0, iters, body, x)
        return v + y[0, 0].astype(jnp.float32) * 1e-6, y

    window = jax.jit(window, donate_argnums=0)
    x = jnp.full((4096, 4096), 0.01, jnp.bfloat16)
    vec = jax.block_until_ready(jnp.arange(n, dtype=jnp.float32) * 1e-3)

    def run_window(iters):
        nonlocal vec
        vec, y = window(vec, x, np.int32(iters))
        return y

    jax.block_until_ready(run_window(8))  # compiled
    t0 = time.perf_counter()
    jax.block_until_ready(run_window(256))
    per_iter = (time.perf_counter() - t0) / 256
    iters = max(8, int(args.busy_s / per_iter))
    out["window_iters"] = iters
    jax.block_until_ready(snapshot(vec))  # compiled

    out["window_alone_s"] = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(run_window(iters))
        out["window_alone_s"].append(time.perf_counter() - t0)

    # the host's base: what `np.asarray` gave for the snapshot before
    # (read-only, and on the CPU backend the device's own memory), and
    # the kept memory the differences are written into
    base = [np.asarray(p) for p in snapshot(vec)]
    scratch = np.zeros(n, np.float32)
    out["host_subtract_128MiB_s"] = []
    for _ in range(reps):
        new = base[0].copy()
        t0 = time.perf_counter()
        np.subtract(new, base[0], out=scratch[: new.shape[0]])
        out["host_subtract_128MiB_s"].append(time.perf_counter() - t0)

    def one(with_window: bool, subtract: str, sending: bool):
        """the snapshot's program, the window behind it, then the
        copies; subtract: "" | "inline" (the copier's thread) |
        "thread" (whoever waits for the piece, here the sender) |
        "own" (a third thread, between the copier and the sender)"""
        nonlocal base
        jax.block_until_ready(vec)
        t0 = time.perf_counter()
        pieces = snapshot(vec)
        y = run_window(iters) if with_window else None
        t_asked = time.perf_counter() - t0
        done = {}

        def watch():
            jax.block_until_ready(y)
            done["window_s"] = time.perf_counter() - t0

        watcher = threading.Thread(target=watch, daemon=True)
        if with_window:
            watcher.start()
        c = Copier(jax, vec, step, 2, pieces=pieces)
        del pieces
        new_base = [None] * len(bounds)

        def diff(i):
            new = c.landed(i)
            lo, hi = bounds[i]
            np.subtract(new, base[i], out=scratch[lo:hi])
            new_base[i] = new
            return scratch[lo:hi]

        if subtract == "inline":
            inner = c._cut  # the copier's own thread does the work

            def run_inline():
                queue, asked = collections.deque(), 0
                c.t_first = time.perf_counter()
                for i in range(len(bounds)):
                    while asked < len(bounds) and asked < i + 2:
                        queue.append(inner(asked))
                        asked += 1
                    new = np.asarray(queue.popleft())
                    lo, hi = bounds[i]
                    np.subtract(new, base[i], out=scratch[lo:hi])
                    new_base[i] = new
                    c._host[i] = scratch[lo:hi]
                    c._events[i].set()
                c.t_last = time.perf_counter()

            c._thread = threading.Thread(target=run_inline, daemon=True)
        get = diff if subtract == "thread" else c.landed
        if subtract == "own":
            diffs = [None] * len(bounds)
            ready = [threading.Event() for _ in bounds]

            def run_own():
                for i in range(len(bounds)):
                    diffs[i] = diff(i)
                    ready[i].set()

            threading.Thread(target=run_own, daemon=True).start()

            def get(i):
                ready[i].wait()
                return diffs[i]

        waited = [0.0]
        c.start()
        if sending:
            send([(lambda i=i: get(i)) for i in range(len(bounds))], waited)
        else:
            for i in range(len(bounds)):
                get(i)
        total = time.perf_counter() - t0
        if with_window:
            watcher.join()
        if subtract:
            base = new_base
        copy_s = c.t_last - c.t_first
        rec = {
            "asked_s": t_asked, "copy_s": copy_s,
            "copy_gbps": n * 4 / copy_s / 1e9, "sync_total_s": total,
        }
        if sending:
            rec["waited_s"] = waited[0]
        rec.update(done)
        return rec

    cases = {
        "copy_alone": (False, "", False),
        "copy_beside_window": (True, "", False),
        "copy_subtract_inline_beside_window": (True, "inline", False),
        "copy_subtract_thread_beside_window": (True, "thread", False),
        "copy_send_beside_window": (True, "", True),
        "copy_subtract_inline_send_beside_window": (True, "inline", True),
        "copy_subtract_thread_send_beside_window": (True, "thread", True),
        "copy_subtract_own_send_beside_window": (True, "own", True),
        "copy_subtract_own_send_alone": (False, "own", True),
    }
    for name, case in cases.items():
        one(*case)  # warm: pages, the receiver's buffer
        out[name] = [one(*case) for _ in range(reps)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--case", default="slices", choices=("slices", "beside"))
    ap.add_argument("--busy_s", type=float, default=2.5)
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

    if args.case == "beside":
        beside(jax, jnp, args, out, send)
        conn.close()
        child.wait(timeout=30)
        _report(out, "d2h_beside_probe.json")
        return

    bump = jax.jit(lambda v: v + 1.0, donate_argnums=0)
    vec = jax.block_until_ready(jnp.arange(n, dtype=jnp.float32))

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
    _report(out, "d2h_stream_probe.json")


def _report(out, name):
    text = json.dumps(out, indent=1)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", name), "w") as f:
        f.write(text)
    print(text)


if __name__ == "__main__":
    main()
