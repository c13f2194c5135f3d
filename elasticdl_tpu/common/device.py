"""Which device a process computes on, and who may hold a chip.

A TPU chip belongs to one process at a time. The master and the
PS/KV/aggregator shards are host math and pin themselves to the CPU
backend (`pin_cpu`); each worker process holds the chips its launcher
gave it (`chip_env`) and nothing else; a process that needs the chip
count without holding a chip asks a short-lived child (`probe_device`).
The CPU is a device only when it was asked for (`JAX_PLATFORMS=cpu`):
`require_device` is the one rule the worker and chip_smoke.py share —
never compute on the CPU by accident.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, List, Sequence, Tuple

ENV_VISIBLE_CHIPS = "TPU_VISIBLE_CHIPS"

# TPU_CHIPS_PER_PROCESS_BOUNDS for a share of n chips (x,y,z extents
# of the sub-mesh one process drives). 1 and 2 ran on the 2x2 v5e host
# (PR 21); 4 is what jax's own multi-process tests use on an 8-chip
# host. A share that is the whole host needs none.
_SHARE_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}


def cpu_requested(env=None) -> bool:
    env = os.environ if env is None else env
    return env.get("JAX_PLATFORMS", "").strip() == "cpu"


def pin_cpu():
    """Host math: this process must never initialise (and so hold) a
    chip its workers need, whatever the environment says."""
    import jax

    jax.config.update("jax_platforms", "cpu")


def device_report() -> dict:
    """platform, device_kind and the chips THIS process holds. A
    process given a share of the host names the physical chips its
    launcher stamped; one that sees the whole host names them by id."""
    import jax

    devs = jax.local_devices()
    visible = os.environ.get(ENV_VISIBLE_CHIPS, "")
    chips = (
        [int(c) for c in visible.split(",") if c.strip()]
        if visible and devs[0].platform == "tpu"
        else [d.id for d in devs]
    )
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "chips": chips,
    }


def require_device(what: str) -> dict:
    """-> device_report(), or exit non-zero when jax found no TPU and
    the CPU was not asked for."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu" and not cpu_requested():
        raise SystemExit(
            f"{what}: jax found no TPU (default backend {backend!r}) and "
            "JAX_PLATFORMS=cpu was not set — refusing to compute on the "
            "CPU by accident"
        )
    return device_report()


def probe_device(timeout: float = 300.0) -> dict:
    """device_report() of a short-lived child: what jax finds on this
    host, learned without this process initialising a backend. The
    child exits (and frees the chips) before this returns."""
    out = subprocess.run(
        [sys.executable, "-m", "elasticdl_tpu.common.device"],
        check=True,
        capture_output=True,
        text=True,
        timeout=timeout,
        env=package_env(os.environ),
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


def package_env(env) -> Dict[str, str]:
    """`env` with this checkout importable regardless of the child's
    cwd."""
    env = dict(env)
    root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    env["PYTHONPATH"] = (
        root + os.pathsep + env["PYTHONPATH"]
        if env.get("PYTHONPATH")
        else root
    )
    return env


# -- one process for each chip -------------------------------------------


def chip_shares(
    chips: Sequence[int], num_processes: int
) -> List[Tuple[int, ...]]:
    """The host's chips divided evenly over `num_processes` worker
    processes: disjoint, equal, in chip order (four workers on four
    chips get one each, one worker gets all four)."""
    per = len(chips) // num_processes
    if per not in _SHARE_BOUNDS and (per == 0 or per != len(chips)):
        raise ValueError(
            f"{num_processes} worker process(es) need a chip each, in even "
            f"shares of {sorted(_SHARE_BOUNDS)} chips or the whole host; "
            f"this host has {len(chips)} ({list(chips)})"
        )
    return [
        tuple(chips[i * per : (i + 1) * per]) for i in range(num_processes)
    ]


def free_share(
    shares: Sequence[Tuple[int, ...]], held: Sequence[Tuple[int, ...]]
) -> Tuple[int, ...]:
    """The first share no live process holds — a replacement takes the
    chips its dead predecessor released."""
    for share in shares:
        if share not in held:
            return share
    raise RuntimeError(
        f"no free chip: all {len(shares)} share(s) {list(shares)} are held "
        "by live worker processes"
    )


def chip_env(share: Sequence[int], host_chips: int) -> Dict[str, str]:
    """Environment that confines one process to `share`. A share that
    is the whole host needs nothing: libtpu's default is every chip."""
    if len(share) == host_chips:
        return {}
    return {
        ENV_VISIBLE_CHIPS: ",".join(str(c) for c in share),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": _SHARE_BOUNDS[len(share)],
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


if __name__ == "__main__":
    print(json.dumps(device_report()))
