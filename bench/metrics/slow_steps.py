"""slow_steps: fused steps of the window whose ``ssv.step.sync`` span (the
host's wait for the device) lasts more than 1.05 x the window's median
``ssv.step.sync``. From the program's spans (``bench/program_trace.py``)."""
import statistics

from bench import program_trace

SLOW = 1.05


def read(run):
    steps = program_trace.window_steps(run)
    if not steps or any(program_trace.SYNC not in kids for _, kids in steps):
        return None
    sync = [kids[program_trace.SYNC] for _, kids in steps]
    limit = SLOW * statistics.median(sync)
    return sum(1 for s in sync if s > limit)
