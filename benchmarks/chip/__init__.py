"""The chip benchmark: `run.py` runs one cell of `BENCHMARK.json` on the
chip; see README.md for its files and how to add to them."""
