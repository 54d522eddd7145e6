"""Device time of the trainer's step program, median over its runs in the
traced window. A control: no change to the store should move it; if it
moves, the device was disturbed and the cycle times beside it are suspect."""

import statistics

from chipbench import trace_reduce

LAYER = "device programs"
UNIT = "s"
SOURCE = "device_trace"
MOVES = "sync_s"


def read(run):
    runs = trace_reduce.program_seconds(run.planes, run.step_program)
    return statistics.median(runs) if runs else None
