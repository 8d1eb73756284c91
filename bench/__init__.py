"""The on-chip benchmark: one cell of ``BENCHMARK.json`` run once by
``python3 bench/run.py``. Everything that measures lives here, apart from
the program it measures."""
