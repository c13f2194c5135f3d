"""How unevenly the sigmoid router loaded the experts held here: the
fullest expert's tokens over the held experts' mean, averaged over the
layers and over the window's `worker.window_stats` spans. 1 is even, 8
is everything on one expert. With the selection bias left at zero
nothing balances the layer (`config.json`, `assumed`), so this and
`held_share` are where its drift shows (`_moe.py`'s reading as it
is)."""

from benchmark.layer_metrics import _moe


def read(run):
    loads = _moe.expert_tokens(run, __file__)
    return None if loads is None else _moe.load_max_over_mean(loads)
