"""Chip benchmark of the FMM: cells, yardstick and trace reduction.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``.  Everything that
decides a number lives here and imports nothing of the program: the
particle generator, the f64 direct-sum reference, the work and byte counts
behind each roofline, the peak table and the reduction of profiler traces.
The program contributes only the entries a window drives.
"""
