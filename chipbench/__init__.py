"""Chip benchmark for the AccMPEG camera-to-server serving path.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the chip it
is started on. Everything the benchmark measures with (traffic, weights
and frames from the seed, the plain reference, the trace reduction, the
peak table and the FLOP/byte functions) lives in this package and
imports nothing from the program except the system under test.
"""
