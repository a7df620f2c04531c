"""moe_device_ms: device ms per fused step under the named scope
``moe.ffn`` (the target's expert layers in the verify pass; the draft has
none), in the traced stretch (``bench/trace.py``'s ``scope_s`` over its
steps). None where the program has no such scope."""
from bench import trace


def read(run):
    return trace.scope_ms_per_step(run.trace, "moe.ffn")
