"""On-chip benchmark of the repository: one cell per run, driven by data.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the TPU it is started on and prints
one JSON result line.  Everything that belongs to one configuration, traffic
mix or per-layer metric lives in its own file under this directory, found
by the name ``BENCHMARK.json`` gives it.
"""
