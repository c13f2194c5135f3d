"""Cross-host aggregation tree (host-local presum aggregators).

An aggregator node terminates its host's worker pushes over the
host's local carrier, presums each rendezvoused cohort with the fan-in math
(master/fanin.presum_f32), and forwards ONE combined delta per cohort
upstream to the PS shard — dropping master fan-in degree from #workers
to #hosts. See agg/aggregator.py for the protocol and
docs/architecture.md "Aggregation tree" for the topology.
"""

from elasticdl_tpu.agg.aggregator import AggregatorServicer  # noqa: F401
from elasticdl_tpu.agg.group import AggGroup  # noqa: F401
