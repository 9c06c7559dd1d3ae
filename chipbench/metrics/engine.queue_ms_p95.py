"""95th percentile (ms) of the engine's own queue span: from a request's
arrival to its batch's first attention step (`decomposition["queue"]`)."""
from chipbench.readers import queue_ms


def read(run):
    return queue_ms(run, 95)
