"""Pallas TPU kernels for the compute hot-spots the paper optimizes:

nsa_verify — fused grouped-query NSA verification (full fusion for reuse
             layers, partial fusion for refresh layers, branch-wise vanilla
             baseline; exact merged-schedule and approximate shared-index
             grouping) + pure-jnp oracle.
flash      — dense tree-verification flash attention (the full-attention
             baseline + draft-model attention) + oracle.
routing    — refresh-layer "Routing Launch" (paper §5.1): fused
             compressed-branch attention + selection-score mapping (one
             normalization yields both) + oracle.

Kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling, scalar-prefetch
block gathers). ``interpret=None`` (the default everywhere) compiles them
with Mosaic on a TPU backend and interprets them on the CPU backend
(``platform.resolve_interpret``). The CPU tests check them against their
oracles in interpret mode; tests/test_tpu_compile.py compiles them for a
described v5e chip at ssv-nsa-1b widths; chip_smoke.py runs the fused
verify layer on the chip against its reference.
"""
from repro.kernels import flash, nsa_verify, routing  # noqa: F401
