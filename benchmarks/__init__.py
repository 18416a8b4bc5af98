"""The on-chip benchmark: cells of BENCHMARK.json, driven by data files.

``python3 -m benchmarks.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell on the attached TPU and prints one JSON line.
``python3 -m benchmarks.rehearse`` runs the same code at the ``tiny`` sizes on
the CPU.  README.md in this directory says how to add a cell with new files
only.
"""
