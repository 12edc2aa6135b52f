"""The benchmark of the compiled fleet drain, from trace to report.

`python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1`
runs one cell of `BENCHMARK.json` on the TPU it is started on.  Everything
here is found by name: a cell `<config>.<traffic>` reads
`bench/configs/<config>.json`, `bench/traffic/<traffic>.json` and
`bench/sizing/<cell>.json`, and each per-layer metric is read by
`bench/metrics/<metric>.py`.  `bench.plainref` is the plain reference that
decides `correct`.
"""
