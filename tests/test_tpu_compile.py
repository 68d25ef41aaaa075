"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU v5e.

Nothing runs: each kernel is lowered and compiled for one chip of a
*described* ``v5e:2x2`` topology, so the TPU compiler's refusals (tiling,
VMEM, unsupported operand types) surface here instead of on the chip. The
kernels compile at the widths the fused runners use, plus the two fused
Pallas dispatches themselves, vmapped over a served batch.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.accel import flexasr as fa, hlscnn as hc
from repro.kernels.af_gemm import af_gemm
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fx_gemm import fx_gemm
from repro.kernels.int8_gemm import int8_gemm

#: samples per served request in the smoke run (the vmapped batch axis)
BATCH = 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    sh = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sh)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel emitted"
    return compiled


def test_af_gemm_compiles(chip):
    # the fused linear leg's per-sample shape: (MAX_TS, MAX_IN) x (MAX_OUT, MAX_IN)
    _compile(
        lambda x, w, b, s: af_gemm(x, w, b, s, s, s, interpret=False),
        chip((fa.MAX_TS, fa.MAX_IN)), chip((fa.MAX_OUT, fa.MAX_IN)),
        chip((fa.MAX_OUT,)), chip(()),
    )


def test_fx_gemm_compiles(chip):
    # the fused conv leg's im2col GEMM: (FOH*FOW, KPAD) x (128, KPAD)
    _compile(
        lambda x, w: fx_gemm(x, w, x_spec=hc.ACT_SPEC, w_spec=hc.W16,
                             o_spec=hc.ACT_SPEC, interpret=False),
        chip((hc.FOH * hc.FOW, hc.KPAD)), chip((128, hc.KPAD)),
    )


def test_int8_gemm_compiles(chip):
    _compile(
        lambda a, b: int8_gemm(a, b, interpret=False),
        chip((512, 512), jnp.int8), chip((512, 512), jnp.int8),
    )


def test_flash_attention_compiles(chip):
    q = chip((1, 4, 512, 128), jnp.bfloat16)
    kv = chip((1, 2, 512, 128), jnp.bfloat16)
    _compile(lambda q, k, v: flash_attention(q, k, v, interpret=False),
             q, kv, kv)


def test_fused_linear_pallas_dispatch_compiles(chip):
    b = (BATCH,)
    _compile(
        fa.fused_linear_pallas(False),
        chip(b + (fa.MAX_TS, fa.MAX_IN)), chip(b), chip(b), chip(b),
        chip((fa.MAX_OUT, fa.MAX_IN)), chip((fa.MAX_OUT,)), chip(()),
        chip((fa.MAX_OUT,)),
    )


def test_fused_conv_pallas_dispatch_compiles(chip):
    _compile(
        hc.fused_conv_pallas(hc.W16, False),
        chip((BATCH, hc.MAX_H, hc.MAX_W, hc.MAX_C)), chip((128, hc.KPAD)),
        chip((hc.MAX_H,)), chip((hc.MAX_W,)), chip((hc.MAX_C,)),
    )


@pytest.fixture(scope="module")
def stream_mesh4(topo):
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(topo.devices), ("stream",))
    batched = NamedSharding(mesh, P("stream"))
    shared = NamedSharding(mesh, P())
    return mesh, (lambda shape: jax.ShapeDtypeStruct(
        shape, jnp.float32, sharding=batched)), (lambda shape: jax.ShapeDtypeStruct(
            shape, jnp.float32, sharding=shared))


def _compile_sharded(fn, *args):
    compiled = _compile(fn, *args)
    # a Mosaic kernel cannot be partitioned by XLA: each chip must run it
    # on its own slice of the batch, never on an all-gathered copy
    assert "all-gather" not in compiled.as_text()
    return compiled


def test_fused_linear_pallas_dispatch_compiles_over_stream_mesh(stream_mesh4):
    mesh, b, s = stream_mesh4
    _compile_sharded(
        fa.fused_linear_pallas(False, mesh),
        b((BATCH, fa.MAX_TS, fa.MAX_IN)), b((BATCH,)), b((BATCH,)),
        b((BATCH,)), s((fa.MAX_OUT, fa.MAX_IN)), s((fa.MAX_OUT,)), s(()),
        s((fa.MAX_OUT,)),
    )


def test_fused_conv_pallas_dispatch_compiles_over_stream_mesh(stream_mesh4):
    mesh, b, s = stream_mesh4
    _compile_sharded(
        hc.fused_conv_pallas(hc.W16, False, mesh),
        b((BATCH, hc.MAX_H, hc.MAX_W, hc.MAX_C)), s((128, hc.KPAD)),
        s((hc.MAX_H,)), s((hc.MAX_W,)), s((hc.MAX_C,)),
    )


def test_tiled_linear_runner_compiles_at_moonlight_widths(chip):
    """The tiled FlexASR linear's runner (plain XLA, the ILA's own data
    path per tile invocation) at a Moonlight-16B-A3B width: the dense MLP's
    down projection, 11264 -> 2048, over a served request of 2 windows of
    256 tokens. FN_START's mode switch folds away (the mode is constant)."""
    O, I, B, C = 2048, 11264, 2, 2
    ot, kt = -(-O // fa.TILE), -(-I // fa.TILE)
    n = ot * kt
    compiled = fa._tiled_run.lower(
        chip((n, fa.TILE, fa.TILE)), chip((n, fa.TILE)), chip((n,)), chip((n,)),
        chip((n,)), chip((B, C, kt, fa.TILE, fa.TILE)), chip((B, C, kt)),
        chip((B, C)), ot=ot, kt=kt, O=O).compile()
    assert "conditional(" not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2e9
