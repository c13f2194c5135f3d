"""The benchmark: elastic PS jobs through `master.main` on the chip.

`python benchmark/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>` runs one cell of `BENCHMARK.json`. Everything that
decides a number lives here, where a PR that claims a gain cannot edit
it: the job driver, the traffic generator, the in-worker probe, the
trace reduction, the FLOP arithmetic, the table of peaks and the
validator of the result line. A configuration, a traffic mix and a
per-layer metric are each a file found by its name in `BENCHMARK.json`
(`configs/<name>/`, `traffic/<name>.json`, `layer_metrics/<name>.py`).
"""
