"""Median per update of the wire: the round trip as the client saw it
(`rpc.client.ReportLocalUpdate` / `rpc.client.ReportGradient`) less
the master's six handler spans of the same update, joined on the
version the response named: gRPC and the socket, both directions. An
update only one side saw is left out (see `_timeline.py`)."""

from benchmark.layer_metrics import _timeline


def read(run):
    timeline = _timeline.load(run, __file__)
    return _timeline.median_ms(_timeline.wire_seconds(timeline))
