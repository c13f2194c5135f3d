"""A window's delta leaves the device in slices, and each slice can
go to the socket while the next is copied out.

The worker's sync used to pay three stages one after the other: the
copy of the whole flat delta off the device (`jax.device_get`), the
frame's way over the socket, the master's `apply`. The first two use
different machinery (the device's DMA and the runtime's threads; two
`memcpy`s on two processes), and a frame's length is known before its
first byte. So the delta is cut into slices on the device, each
slice's `copy_to_host_async` is asked a bounded number ahead, and the
request carries a `codec.LeafVector` whose pieces are
`codec.PendingPiece`s: the socket carrier sends each as it lands
(`transport._send_parts`), a carrier that needs one buffer waits for
all of them and joins, as it always joined.

`DeltaStream` is the copies' side of that: one short-lived thread a
sync, which asks for the slices in order and hands each to whoever
waits for it.

What is cut into slices on the device is the delta itself on the
overlapped chain (`Worker._delta_in_slices`). The serial chain, whose
device has no room for a second vector beside a window, forms no delta
on the device at all (PR 59): the base of window n+1 IS the model at
the end of window n, a snapshot the device holds through window n+1
anyway, so the stream copies out THAT, in the same slices, while window
n+1 runs, and the delta is formed here, on the host: a landed slice
less the slice that landed for the sync before (`base`), float32, into
memory the worker keeps from sync to sync (`out`); the landed slices
are the next sync's base (`snapshot()`). The request, the frame, the
master and the answer see the same float32 delta either way. The
host's float32 subtraction is IEEE's and so is the device's, bit for
bit, but for one thing: a TPU flushes a subnormal difference to zero
(|d| < 1.18e-38) and the host keeps it. Both are exact on the CPU,
where the tests hold the two forms bit-identical.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Iterator, List, Tuple

import numpy as np

from elasticdl_tpu.common import codec

#: The bytes of one slice of a float32 delta, and how many slices'
#: copies are asked for at a time. Chosen on the chip's host by
#: `scripts/d2h_stream_probe.py` (PERF.md, PR 45: a 1,877 MB vector
#: off a v5e, copy alone | copy and send to a second process, seconds):
#: whole 0.53-0.73 | 1.21-1.30 one after the other; two in flight of
#: 16 / 32 / 64 MiB 1.2 | 1.0-1.6 (a slice under about 100 MiB copies
#: at half the rate, whatever pages it lands in), 96 MiB 0.62 | 0.76,
#: 128 MiB 0.55 | 0.70, 256 MiB 0.56 | 0.74; one in flight of 128 MiB
#: 0.67 | 0.86, three 0.54 | 0.71, 64 MiB x 8 0.64 | 0.92. Two slices
#: of 128 MiB are also what the sync's moment adds to the device's
#: memory on the serial chain: 268 MB.
DELTA_SLICE_BYTES = 128 << 20
SLICES_IN_FLIGHT = 2


def slice_bounds(n: int) -> List[Tuple[int, int]]:
    """[lo, hi) of each slice of a float32 vector of n elements: equal
    slices of `DELTA_SLICE_BYTES` and a shorter tail."""
    step = DELTA_SLICE_BYTES // 4
    return [(lo, min(lo + step, n)) for lo in range(0, n, step)]


class DeltaStream:
    """The copies of one sync's slices off the device.

    `slices` yields the device array of each slice in order (cut by a
    program as it is asked for, or cut earlier and let go here); the
    stream's own thread asks for the next one at most
    `SLICES_IN_FLIGHT` slices beyond the one being waited for, which is
    also how many copies are in flight and how many slices the device
    holds for the stream at a time. A slice that has landed is a host
    array of its own and its device array is let go.

    `vector()` is the delta as the request carries it. `settle()`
    waits for the thread and says when the first slice was asked for
    and when the last one landed (`time.time()`), for the sync's one
    `worker.d2h` span. A copy that fails fails every piece not yet
    landed, with the error that stopped it.

    `base`, where given, makes the slices a SNAPSHOT's and the pieces
    differences: piece i is what landed less `base[i]` (the host
    arrays an earlier stream's `snapshot()` gave), formed on the
    stream's own thread as each slice lands, into `out[lo:hi]` where
    the caller keeps memory for it (a landed array is the runtime's and
    read-only, so nothing is subtracted in place). The list is the
    stream's from then on: each old slice is let go as it is used.
    `snapshot()` gives the landed slices, the next base, once `settle()`
    has returned, or None if a copy failed; `subtracting()` says
    from when to when the subtractions ran and how long they took."""

    def __init__(self, bounds, slices: Iterator, base=None, out=None):
        self._bounds = list(bounds)
        self._slices = slices
        self._base, self._out = base, out
        self._landed = [None] * len(self._bounds) if base is not None else None
        self._subtracting = [0.0, 0.0, 0.0]  # first began, last ended, busy
        # what the stream's thread hands to whoever waits, under `_cond`
        self._cond = threading.Condition()
        self._host = [None] * len(self._bounds)
        self._error = None
        self._t_first = self._t_last = 0.0
        self._thread = threading.Thread(
            target=self._copy, name="delta-stream", daemon=True
        )

    def start(self) -> None:
        self._thread.start()

    def _copy(self):
        count = len(self._bounds)
        slices, self._slices = self._slices, None
        in_flight = collections.deque()
        piece = None
        asked = 0
        with self._cond:
            self._t_first = self._t_last = time.time()
        try:
            for i in range(count):
                while asked < count and asked < i + SLICES_IN_FLIGHT:
                    piece = next(slices)
                    piece.copy_to_host_async()
                    in_flight.append(piece)
                    asked += 1
                # blocks until the copy has landed; the device's slice
                # goes with this reference, the host's copy stays
                host = np.asarray(in_flight.popleft())
                landed = time.time()
                snapshot = self._base is not None
                piece = self._less_base(i, host, landed) if snapshot else host
                with self._cond:
                    self._host[i] = piece
                    if snapshot:
                        self._landed[i] = host
                    self._t_last = landed
                    self._cond.notify_all()
        except BaseException as e:  # handed to whoever waits, not lost
            # (with its traceback, which keeps this frame: let go here
            # of the slices it still holds)
            in_flight.clear()
            piece = None
            with self._cond:
                self._error = e
                self._cond.notify_all()

    def _less_base(self, i: int, new, began: float):
        """Slice i of the delta: what landed less the base's slice,
        which is let go."""
        lo, hi = self._bounds[i]
        out = None if self._out is None else self._out[lo:hi]
        old, self._base[i] = self._base[i], None
        piece = np.subtract(new, old, out=out)
        ended = time.time()
        took = self._subtracting
        took[0] = took[0] or began
        took[1], took[2] = ended, took[2] + ended - began
        return piece

    def _wait(self, i: int, timeout):
        with self._cond:
            if not self._cond.wait_for(
                lambda: self._host[i] is not None or self._error is not None,
                timeout,
            ):
                raise TimeoutError(
                    f"slice {i} of {len(self._bounds)} of the delta has not "
                    f"left the device after {timeout:.3f}s"
                )
            arr, error = self._host[i], self._error
        if arr is None:
            raise RuntimeError(
                f"slice {i} of {len(self._bounds)} of the delta did not "
                f"land: {error!r}"
            ) from error
        return arr

    def vector(self) -> codec.LeafVector:
        return codec.LeafVector(
            [
                codec.PendingPiece(hi - lo, lambda t, i=i: self._wait(i, t))
                for i, (lo, hi) in enumerate(self._bounds)
            ]
        )

    def settle(self) -> Tuple[float, float]:
        self._thread.join()
        with self._cond:
            return self._t_first, self._t_last

    def snapshot(self):
        """The landed slices of a stream that was given a `base`."""
        with self._cond:
            if self._error is not None or self._landed is None:
                return None
            return None if any(a is None for a in self._landed) else self._landed

    def subtracting(self) -> Tuple[float, float, float]:
        with self._cond:
            return tuple(self._subtracting)
