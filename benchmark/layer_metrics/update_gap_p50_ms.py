"""Median time between consecutive updates applied at the master (`ts`
of the sink's `train/loss` events inside the window): in per-step mode
one whole round trip of a step — gradient up, optimizer, model down,
the next step's compute. The steady statistic beside `goodput` there;
a window of such a cell holds some tens of updates, too few for a tail."""


def read(run):
    gaps = sorted(run["update_gaps_ms"])
    if len(gaps) < 5:
        return None
    middle = len(gaps) // 2
    return gaps[middle] if len(gaps) % 2 else (gaps[middle - 1] + gaps[middle]) / 2
