"""step_host_ms: median, over the window's fused steps, of the host time the
program's ``ssv.step`` span spends outside its ``ssv.step.sync`` child (the
wait for the device): argument preparation, launch, host state update, in
ms. From the program's spans (``bench/program_trace.py``)."""
import statistics

from bench import program_trace


def read(run):
    steps = program_trace.window_steps(run)
    if not steps or any(program_trace.SYNC not in kids for _, kids in steps):
        return None
    return 1e3 * statistics.median(total - kids[program_trace.SYNC]
                                   for total, kids in steps)
