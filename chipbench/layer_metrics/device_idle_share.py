"""The share of the traced window in which no operation ran on the device:
1 - busy / window, from the profiler's trace, averaged over the chips. In a
closed loop it is the share of a cycle in which the chip waits for the
store (and for the benchmark's own check)."""

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "sync_s"


def read(run):
    if not run.device or not run.device["window_s"]:
        return None
    return 100.0 * (1.0 - run.device["busy_s"] / run.device["window_s"])
