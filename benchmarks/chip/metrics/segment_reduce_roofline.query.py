"""segment_reduce_roofline.query: the segmented-reduction kernel
(kernels/segment_reduce.py) against its roofline, which is memory: the
bytes its calls in the window need (values and segment ids read, outputs
written) at peak bandwidth, over the device time of its programs."""
import devtrace
from peakshare import roofline_share


def read(run):
    if run.trace is None:
        return None
    return roofline_share(run, 0, run.counters.get("segment_reduce_bytes", 0),
                          devtrace.device_seconds(run.trace,
                                                  "segment_reduce"))
