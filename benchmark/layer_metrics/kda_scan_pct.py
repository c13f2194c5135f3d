"""Share of the probe's traced slice's device-busy time in leaf
operations under `kda/scan`: the chunked delta-rule recurrence alone
(`intra`: the decayed triangles and the triangular system of every
chunk; `state`: the pass over the chunks), all phases (see
`_hybrid.py`)."""

from benchmark.layer_metrics import _hybrid


def read(run):
    return _hybrid.share(run, __file__, "kda_scan")
