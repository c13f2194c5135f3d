"""What the compiler set aside for the temporaries of the program that
trains (the window, or the step in per-step mode), GB: `memory.temp`
of `compiled.memory_analysis()`, as the worker wrote it beside the
program's map (see `_step.py`). Known once the program is compiled;
the most of any worker's (0: the map states none)."""

from benchmark.layer_metrics import _step


def read(run):
    return _step.temp_gb(run, __file__)
