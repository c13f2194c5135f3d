"""How unevenly the early router loaded the eight experts held here:
the fullest expert's tokens over the held experts' mean, averaged over
the four expert layers and over the window's `worker.window_stats`
spans. 1 is even, 8 is everything on one expert. Nothing balances this
router (`config.json`, `assumed`: no balance term); this, `held_share`
and `route_rows` are where its drift shows (`_moe.py`'s reading as it
is)."""

from benchmark.layer_metrics import _moe


def read(run):
    loads = _moe.expert_tokens(run, __file__)
    return None if loads is None else _moe.load_max_over_mean(loads)
