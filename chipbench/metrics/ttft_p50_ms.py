"""Median TTFT (ms) over every request due in the window."""
from chipbench.readers import ttft_percentile


def read(run):
    return ttft_percentile(run, 50)
