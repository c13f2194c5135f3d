"""One reader per per-layer metric: `<metric name>.py` with
`read(run) -> number | None`, found by the name in `BENCHMARK.json`. A
reader that finds nothing to read returns None and the metric is left
out of the line.

`run` is what `benchmark/run.py` measured:
- `platform`, `chips`, `sizes` (the configuration's file), `mix`;
- `window`: `window_s`, `wall0`/`wall1`, `master_cpu_s`;
- `snaps`: the window's polls of `GetSchedStats`, each `t`, `wall`,
  `completed`, `recomputed`, `version`, `fractions` (the workers'
  phase shares over their last 30 s, or None), `relaunches`;
- `goodput_records`, `goodput_span_s`: what `goodput` divides;
- `update_gaps_ms`, `setup_s`;
- `trace`: the merged reduction of the probes' traces (`window_s`,
  `busy_s`, `device_ops`, `idle_gaps`), None in an untraced run;
- `flops_per_sample`, `peak_flops_per_s` (None off the TPU).
"""
