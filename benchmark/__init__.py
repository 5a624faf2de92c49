"""The chip benchmark: the yardstick every later PR is measured with.

``python -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell once and prints one JSON line.  Cells,
configurations and metrics are listed in ``BENCHMARK.json`` at the root
of the checkout; everything that belongs to one of them is a file of its
own under this directory, found by name (see ``harness.py``).  Nothing
here is imported by the program, and the references under ``reference/``
import nothing of the program.
"""
