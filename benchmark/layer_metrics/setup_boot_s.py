"""The master's process start to the end of the first worker's
`setup.backend_init` (jax and the TPU initialised): imports, the
`probe_device` child, the spawn, the worker's own imports (see
`_timeline.py`)."""

from benchmark.layer_metrics import _timeline


def read(run):
    return _timeline.setup_boot_s(_timeline.load(run, __file__))
