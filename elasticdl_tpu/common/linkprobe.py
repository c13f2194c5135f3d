"""Link-weather tracking for the adaptive sync plane.

"Link weather" is an estimate of the worker<->master/PS network link
bandwidth that the sync plane rides on. ``LinkWeather`` is the passive
tracker the worker's sync thread feeds from the push timing it already
has: every window push knows how many wire bytes it sent and how long
the RPC took; that ratio IS a bandwidth sample, with zero extra
traffic. The tracker keeps a short ring of recent samples and exposes
a median-of-recent estimate that is robust to the occasional stalled
push.

The pure per-round wire-form decision lives in sync_policy.decide();
this module only measures.
"""

from __future__ import annotations

import threading
from collections import deque


class LinkWeather:
    """Passive link-bandwidth tracker fed from sync-push timings.

    Thread contract: ``observe`` is called from the worker's sync
    threads (one at a time per worker — the sync chain serializes
    pushes), ``mbps``/``history`` may be read from any thread. A small
    internal lock covers the ring; no caller-visible locking.
    """

    def __init__(self, window: int = 8):
        self._lock = threading.Lock()
        self._samples: deque[float] = deque(maxlen=max(1, int(window)))
        self._observations = 0

    def observe(self, wire_bytes: int, seconds: float) -> None:
        """Record one push: `wire_bytes` payload bytes took `seconds`.

        Sub-millisecond or zero-byte pushes are discarded — they
        measure dispatch overhead, not the link."""
        if wire_bytes <= 0 or seconds <= 1e-3:
            return
        mbps = wire_bytes * 8.0 / (seconds * 1e6)
        with self._lock:
            self._samples.append(mbps)
            self._observations += 1

    def mbps(self) -> float | None:
        """Median of the recent samples, or None before any sample —
        callers (sync_policy.decide) must handle the cold start."""
        with self._lock:
            if not self._samples:
                return None
            ordered = sorted(self._samples)
        mid = len(ordered) // 2
        if len(ordered) % 2:
            return ordered[mid]
        return (ordered[mid - 1] + ordered[mid]) / 2.0

    def history(self) -> list[float]:
        """Recent raw samples, oldest first (for decide()'s hysteresis
        and the worker's decision log)."""
        with self._lock:
            return list(self._samples)

    @property
    def observations(self) -> int:
        with self._lock:
            return self._observations
