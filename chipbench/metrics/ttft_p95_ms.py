"""95th percentile of TTFT (ms) over every request due in the window;
one that never came counts beyond every finished one."""
from chipbench.readers import ttft_percentile


def read(run):
    return ttft_percentile(run, 95)
