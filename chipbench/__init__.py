"""The chip benchmark: everything `BENCHMARK.json` runs lives in this
directory. See `run.py` for the command and `PERF.md` for what is measured."""
