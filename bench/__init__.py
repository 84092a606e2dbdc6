"""Serving benchmark on the chip: ``python3 bench/run.py --workload <cell> ...``."""
