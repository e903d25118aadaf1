"""HALCONE chip benchmark (see bench/run.py)."""
