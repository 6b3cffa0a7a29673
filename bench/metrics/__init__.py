"""Per-layer metric readers, one ``<metric>.py`` each, found by the
metric's name in BENCHMARK.json. Each defines ``read(ctx)``: the metric's
value from the traced run's spans, counters and device trace
(``bench.trace.Context``), or None where there is nothing to read, and the
harness then leaves the metric out of the line."""
