"""Shared code of the benchmark: everything a cell's run needs that is not
the system under test. Nothing here imports the program at import time."""
