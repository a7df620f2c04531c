"""step_mfu: required FLOPs of the window's fused steps over the summed
host durations of their ``step()`` calls (each ends in its own host sync)
and the chip's bf16 peak, in percent."""
from bench import flops


def read(run):
    steps = run.window_steps()
    if not steps:
        return None
    work = sum(flops.step_flops(run.cfg, s.contexts) for s in steps)
    busy = sum(s.t1 - s.t0 for s in steps)
    return 100.0 * work / busy / run.peaks["bf16_flops_per_s"]
