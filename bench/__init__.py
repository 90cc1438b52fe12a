"""The benchmark: one federated round on the chip, measured through the
program's entry point (see bench/run.py)."""
