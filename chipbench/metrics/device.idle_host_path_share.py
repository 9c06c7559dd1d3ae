"""Share of the traced window, %, in which the device is idle while a
thread of the program is inside one of its `asap.*` work spans: the device
waiting on the host data path.  Read from the run's own trace file; None
without a trace or where the program opens no such span."""
from chipbench import bench, spans


def read(run):
    if run.reduced is None:
        return None
    return spans.idle_host_path_share(spans.load(bench.TRACE_DIR))
