"""commit_device_ms: device ms per fused step under the named scope
``ssv.commit`` (the accepted path written into the target's and the draft's
KV pools), in the traced stretch (``bench/trace.py``'s ``scope_s`` over its
steps)."""
from bench import trace


def read(run):
    return trace.scope_ms_per_step(run.trace, "ssv.commit")
