"""The chip benchmark of the density service.

``python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the machine it
is started on and prints one JSON result line.  Everything the benchmark
measures with lives here, apart from the program: the stream generator,
the starting pool, the plain reference, the work counts, the table of
peaks and the reduction of a profiler trace.  Configurations, traffic
mixes, drivers and per-layer metrics are files found by their names.
"""
