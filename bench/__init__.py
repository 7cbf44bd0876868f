"""On-chip benchmark of the device sweep: cells, traffic, reference, metrics.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace
<0|1>`` runs one cell of ``BENCHMARK.json`` once on the accelerator of the
machine it starts on and prints one JSON result line. Everything that
measures (traffic generation, the plain reference and the comparison
that decides ``correct``, the reduction from profiler trace to metrics,
the table of device peaks) lives in this directory; from the program it
takes only the system under test.
"""
