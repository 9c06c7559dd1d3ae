"""Chip benchmark of the ASAP prefill server: traffic, reference, reduction.

Everything that decides a number lives here, apart from the program under
test: the traffic generator, the weights drawn from the seed, the plain
float32 reference, the FLOP and byte counts, the table of peaks and the
reduction of a profiler trace.  `run.py` is the one command; cells,
configurations, traffic mixes and metrics are files found by name (see
`manifest.py`).
"""
