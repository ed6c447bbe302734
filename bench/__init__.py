"""The chip benchmark: ``python3 bench/run.py --workload W --seed N --seconds S --trace 0|1``."""
