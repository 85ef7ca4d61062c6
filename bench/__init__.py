"""The chip benchmark: cells of deployment x traffic, driven by data.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the chip and prints one JSON line.
Everything here is the yardstick: deployments and traffic are generated
from the seed, the plain reference decides ``correct``, and the program
under test (``src/repro``) receives only the generated inputs.
"""
