"""session_window_roofline.query: the session-window program
(kernels/session_window.py) against its roofline, which is memory: the
bytes its calls in the window need (each row's user, timestamp, item and
click type read, the item categories read and one count per item
written; padding rows not counted) at peak bandwidth, over the device
time of its programs."""
import devtrace
from peakshare import roofline_share


def read(run):
    if run.trace is None:
        return None
    return roofline_share(run, 0, run.counters.get("session_window_bytes", 0),
                          devtrace.device_seconds(run.trace,
                                                  "session_window"))
