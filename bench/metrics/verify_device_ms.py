"""verify_device_ms: device ms per fused step under the named scope
``ssv.verify`` (the target's tree-masked verify pass, its ``nsa.*`` scopes
included), in the traced stretch (``bench/trace.py``'s ``scope_s`` over its
steps)."""
from bench import trace


def read(run):
    return trace.scope_ms_per_step(run.trace, "ssv.verify")
