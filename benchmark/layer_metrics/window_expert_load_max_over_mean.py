"""How unevenly the softmax router loaded the sixteen experts held
here: the fullest expert's tokens over the held experts' mean, averaged
over the four expert layers and over the window's
`worker.window_stats` spans. 1 is even, 16 is everything on one expert.
The configuration states no balance term (`config.json`, `assumed`), so
nothing in the loss evens the layer: this, `held_share` and `route_rows`
are where a drift shows, and the grouped matmuls of 512 wide pay for
their fullest group (`_moe.py`'s reading as it is)."""

from benchmark.layer_metrics import _moe


def read(run):
    loads = _moe.expert_tokens(run, __file__)
    return None if loads is None else _moe.load_max_over_mean(loads)
