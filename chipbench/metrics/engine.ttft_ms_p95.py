"""95th percentile of TTFT (ms) over every request due in the window; one
that never came counts beyond every finished one.  In a 51 s window of 48
requests a host stall of a second or two moves it by about as much, so it
is read per layer, beside the end-to-end median."""
from chipbench.readers import ttft_percentile


def read(run):
    return ttft_percentile(run, 95)
