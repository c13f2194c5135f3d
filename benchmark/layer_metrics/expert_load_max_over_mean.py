"""How unevenly the router loaded the experts held here: the fullest
expert's tokens over the held experts' mean, averaged over the layers
and over the window's `worker.window_stats` spans (each the window's
last step). 1 is even, 8 is everything on one expert; the slowest
expert is what an expert-parallel step waits for (see `_moe.py`)."""

from benchmark.layer_metrics import _moe


def read(run):
    loads = _moe.expert_tokens(run, __file__)
    return None if loads is None else _moe.load_max_over_mean(loads)
