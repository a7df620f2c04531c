"""moe_roofline: the expert layers' roofline share in the fused step, in
percent: the least time the chip could take for their work per step
(``bench/expert_work.py``, from shapes) over the device time under
``moe.ffn`` per step. None where the program has no such scope."""
from bench import expert_work, trace


def read(run):
    ms = trace.scope_ms_per_step(run.trace, "moe.ffn")
    if not ms:
        return None
    return 100.0 * 1e3 * expert_work.floor_s(run.cfg, run.mix, run.peaks) / ms
