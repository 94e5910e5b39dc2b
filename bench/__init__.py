"""On-chip training benchmark for the Piper MoE training system.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1``
runs one cell of ``BENCHMARK.json`` once and prints one JSON result line.
Everything that defines the measurement lives here: traffic generation,
the weights made from the seed, the plain float32 reference that decides
``correct``, the FLOP and byte counters, the table of peaks and the
reduction from profiler traces to per-layer metrics.  Only
``bench/program.py`` imports the system under test (``src/repro``).
"""
