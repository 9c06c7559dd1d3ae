"""Chip benchmark of the ASAP prefill server: traffic, reference, reduction.

Everything that decides a number lives here, apart from the program under
test: the traffic generator, the table of peaks, the kernel's FLOP and byte
counts and the reduction of a profiler trace, and for each model
architecture a reference module (the weights drawn from the seed, the plain
float32 reference, the model's FLOPs and routed rows).  `run.py` is the one
command; cells, configurations, reference modules, traffic mixes and
metrics are files found by name (see `manifest.py`).
"""
