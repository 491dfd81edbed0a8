"""The chip benchmark of this repository.

`BENCHMARK.json` at the root lists configurations, cells and metrics;
`python3 -m bench.run` runs one cell once (see `bench.run`). What belongs
to one configuration, traffic mix, metric or program is a file of its
own under this directory, found by name (see `bench.spec`).
"""
