"""The chip benchmark of the SPC5 library (``python3 bench/run.py``).

Everything that decides a number lives here, apart from the program it
measures: traffic generation, the plain references, the table of peaks,
the operation and byte counts, and the reduction from traces to metrics.
"""
