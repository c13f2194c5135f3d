"""Test env: hermetic CPU-backend JAX with a virtual 8-device mesh.

Mirrors the reference's testing posture — multi-node semantics tested
on one machine (SURVEY §4.3) — using
`--xla_force_host_platform_device_count=8` so sharding/collective code
paths run without TPUs. TPU-gated tests opt in via EDL_TPU_TESTS=1,
following the reference's K8S_TESTS env-switch pattern
(elasticdl/python/tests/k8s_client_test.py:20-23).
"""

import os

# Force, don't default: unit tests run on the CPU backend even on a
# machine that holds a chip (a chip belongs to one process at a time,
# and the suite starts many), with eight virtual CPU devices for the
# mesh tests. Every process a test spawns inherits both variables.
# TPU-gated tests (EDL_TPU_TESTS=1) drop JAX_PLATFORMS for the child
# that needs the chip; this process never initialises it.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import atexit  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402

# Every RpcServer opens a Unix-socket listener in EDL_UDS_DIR (the
# system temp dir when unset), named by its port. Each pytest process
# gets a directory of its own (an xdist worker inherits the
# controller's environment, so whatever is set is replaced), so the
# sweep below never reads another worker's live socket as this test's
# leak, and everything a test spawns inherits it. Short, because an
# AF_UNIX path holds 108 bytes; removed when the process ends.
_uds_dir = tempfile.mkdtemp(prefix="edl-t-")
os.environ["EDL_UDS_DIR"] = _uds_dir
atexit.register(shutil.rmtree, _uds_dir, ignore_errors=True)

# -- OS-resource leak sweep ----------------------------------------------------
#
# The transport/chaos/migration suites spawn real servers, each with
# an AF_UNIX listener; a teardown bug there leaks its socket file
# (docs/fault_model.md, SIGKILL reclamation) and, the name being the
# port's, poisons a LATER test that is handed the port again. The
# sweep snapshots this process's socket directory around each test in
# the suites that own such servers and fails loud with the leaked
# names — the runtime counterpart of the static `resource-lifecycle`
# family.

_SWEPT_MODULES = frozenset({
    "test_transport",
    "test_chaos",
    "test_scenario",
    "test_migration",
    "test_process_job",
})
_LEAK_GRACE_SECS = 5.0


def _stray_uds():
    try:
        names = os.listdir(_uds_dir)
    except OSError:
        return frozenset()
    return frozenset(n for n in names if n.startswith("edl-uds-"))


@pytest.fixture(autouse=True)
def _os_resource_sweep(request):
    if request.module.__name__ not in _SWEPT_MODULES:
        yield
        return
    before = _stray_uds()
    yield
    # daemon reaper threads (subprocess transports, deferred unlinks)
    # may lag the test body by a beat; poll before declaring a leak
    deadline = time.monotonic() + _LEAK_GRACE_SECS
    while True:
        leaked = _stray_uds() - before
        if not leaked:
            return
        if time.monotonic() >= deadline:
            break
        time.sleep(0.05)
    pytest.fail(
        f"{request.node.nodeid} leaked transport sockets: {sorted(leaked)}"
    )
