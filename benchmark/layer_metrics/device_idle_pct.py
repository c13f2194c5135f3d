"""Share of the probe's traced slice in which no operation ran on the
device, mean over the cell's chips: 100 x (1 - busy_s / window_s)."""


def read(run):
    if run["platform"] != "tpu" or not run["trace"]:
        return None
    return 100.0 * (1.0 - run["trace"]["busy_s"] / run["trace"]["window_s"])
