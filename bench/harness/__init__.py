"""The benchmark's general code: cells from `BENCHMARK.json`, data from the
configuration files, load from the traffic files, the comparison that decides
`correct`, and the reduction of a profiler trace.  Whatever belongs to one
configuration, traffic mix or per-layer metric lives in a file of its own
under `bench/configs`, `bench/traffic` or `bench/metrics`."""
