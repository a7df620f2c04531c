"""Compile-only checks: the main-path Pallas kernels build for a TPU v5e at
the ``ssv-nsa-1b`` widths (Hq 32, Hkv 8, Dh 64, bf16 KV) and a 16K context.

Nothing runs: each kernel is lowered with ``interpret=False`` for a
described (not attached) v5e chip and compiled by the TPU compiler, which
raises what the chip's compiler would raise (block tiling, VMEM limits,
unsupported vector ops). The topology is described inside a module fixture,
never at import, so every test worker collects the same tests.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.ssv_nsa_1b import CONFIG
from repro.kernels.flash import ops as flash_ops
from repro.kernels.nsa_verify import ops as verify_ops
from repro.kernels.routing import ops as routing_ops

NSA = CONFIG.nsa
B, T, S = 1, 8, 16384
HQ, HKV, DH = CONFIG.num_heads, CONFIG.num_kv_heads, CONFIG.head_dim
NCB = NSA.num_cmp_blocks(S)
N_SEL = NSA.n_selected


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile_has_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _verify_args(spec, kv_shape):
    return [spec((B, T, HQ, DH), jnp.float32),            # q
            spec(kv_shape), spec(kv_shape),               # k/v cache or pool
            spec((B, NCB, HKV, DH)), spec((B, NCB, HKV, DH)),
            spec((B, T, HKV, DH)), spec((B, T, HKV, DH)),  # draft k/v
            spec((B, T, HKV, N_SEL), jnp.int32),
            spec((B, T, HKV, N_SEL), jnp.bool_),
            spec((B, T), jnp.int32), spec((), jnp.int32), spec((), jnp.int32),
            spec((B, T, T), jnp.bool_), spec((B, T, 3, HQ), jnp.float32)]


@pytest.mark.parametrize("variant", [
    dict(C=2, mode="exact"),                                  # reuse: full fusion
    dict(C=2, mode="exact", include_cmp=False),               # refresh: partial
    dict(C=4, mode="approx"),
    dict(C=1, mode="exact", include_cmp=False, include_win=False,
         combine=False),                                      # vanilla slc
    dict(C=1, mode="exact", include_cmp=False, include_sel=False,
         combine=False),                                      # vanilla win
], ids=["reuse", "refresh", "approx", "vanilla_slc", "vanilla_win"])
def test_nsa_verify_kernel_compiles(spec, variant):
    has_cmp_in = not variant.get("include_cmp", True) and \
        variant.get("combine", True)

    def f(*args, o_cmp=None):
        return verify_ops.nsa_verify_fused(*args, NSA, interpret=False,
                                           o_cmp_in=o_cmp, **variant)
    args = _verify_args(spec, (B, S, HKV, DH))
    if has_cmp_in:
        args.append(spec((B, T, HQ, DH), jnp.float32))
        _compile_has_kernel(lambda *a: f(*a[:-1], o_cmp=a[-1]), *args)
    else:
        _compile_has_kernel(f, *args)


def test_nsa_verify_kernel_compiles_paged(spec):
    page = NSA.sel_block
    max_pages = S // page

    def f(*args):
        *rest, pages = args
        return verify_ops.nsa_verify_fused(*rest, NSA, C=2, mode="exact",
                                           interpret=False, page_table=pages)
    args = _verify_args(spec, (2 * max_pages, page, HKV, DH))
    _compile_has_kernel(f, *args, spec((B, max_pages), jnp.int32))


def test_routing_kernel_compiles(spec):
    f = functools.partial(routing_ops.routing_fused, nsa=NSA, kv_len=S,
                          interpret=False)
    _compile_has_kernel(
        lambda q, k, v, pos, ncb: f(q, k, v, pos, ncb),
        spec((B, T, HQ, DH), jnp.float32), spec((B, NCB, HKV, DH)),
        spec((B, NCB, HKV, DH)), spec((B, T), jnp.int32), spec((), jnp.int32))


@pytest.mark.parametrize("window", [0, NSA.window])
def test_flash_kernel_compiles(spec, window):
    def f(q, k, v, kd, vd, pos, prefix, tm):
        return flash_ops.flash_verify(q, k, v, kd, vd, pos, prefix, tm,
                                      window=window, interpret=False)
    _compile_has_kernel(
        f, spec((B, T, HQ, DH), jnp.float32), spec((B, S, HKV, DH)),
        spec((B, S, HKV, DH)), spec((B, T, HKV, DH)), spec((B, T, HKV, DH)),
        spec((B, T), jnp.int32), spec((), jnp.int32),
        spec((B, T, T), jnp.bool_))
