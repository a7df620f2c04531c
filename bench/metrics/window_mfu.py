"""window_mfu: required FLOPs of every fused step of the window
(bench/flops.py) over the window's seconds and the chip's bf16 peak, in
percent."""
from bench import flops


def read(run):
    steps = run.window_steps()
    if not steps:
        return None
    w0, w1 = run.window
    work = sum(flops.step_flops(run.cfg, s.contexts) for s in steps)
    return 100.0 * work / (w1 - w0) / run.peaks["bf16_flops_per_s"]
