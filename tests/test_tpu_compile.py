"""Mosaic compile checks for the Pallas kernels at real widths.

Each test compiles one kernel for a described (not attached) TPU v5e —
the ``v5e:2x2`` topology that JAX can describe without the chip — and
asserts that the program holds a Mosaic kernel (``tpu_custom_call``).  The
compiler refuses here what interpret mode accepts: blocks that are not
aligned to the (sublane, lane) tiling, scratch in a memory space Mosaic
cannot allocate, a collective kernel without its barrier.  Nothing runs,
so these say nothing about results or speed.

The fused ``ag_gemm``/``gemm_rs`` shapes are ``minicpm_2b`` at tp=4
(d_model 2304, d_ff 5760, 36 heads of 64): FFN ``n = 5760/4 = 1440``, QKV
``n = 3*2304/4 = 1728``, o-proj ``k = 2304/4 = 576``.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import compat

BF16 = jnp.bfloat16
TP = 4
D_MODEL, D_FF, HEADS, HEAD_DIM = 2304, 5760, 36, 64
ROWS = 512                      # tokens per shard: batch 2 x seq 1024 / 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(topo.devices, ("model",))


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k,n", [(D_MODEL, D_FF // TP),          # FFN w1
                                 (D_MODEL, 2 * D_FF // TP),      # FFN w1|w3
                                 (D_MODEL, 3 * D_MODEL // TP)],  # QKV
                         ids=["ffn", "ffn_w13", "qkv"])
def test_ag_gemm_compiles(mesh, k, n):
    from repro.kernels import ops

    def f(x, w):
        return ops.ag_matmul_fused(x, w, axis_name="model", n_dev=TP,
                                   interpret=False)
    sm = compat.shard_map(f, mesh=mesh,
                          in_specs=(P("model", None), P(None, "model")),
                          out_specs=P(None, "model"), check_vma=False)
    _assert_mosaic(sm,
                   _sds((TP * ROWS, k), NamedSharding(mesh, P("model", None))),
                   _sds((k, TP * n), NamedSharding(mesh, P(None, "model"))))


@pytest.mark.parametrize("k_sh", [D_FF // TP, D_MODEL // TP],
                         ids=["ffn_w2", "o_proj"])
def test_gemm_rs_compiles(mesh, k_sh):
    from repro.kernels import ops

    def f(y, w):
        return ops.matmul_rs_fused(y, w, axis_name="model", n_dev=TP,
                                   interpret=False)
    sm = compat.shard_map(f, mesh=mesh,
                          in_specs=(P(None, "model"), P("model", None)),
                          out_specs=P("model", None), check_vma=False)
    _assert_mosaic(
        sm,
        _sds((TP * ROWS, TP * k_sh), NamedSharding(mesh, P(None, "model"))),
        _sds((TP * k_sh, D_MODEL), NamedSharding(mesh, P("model", None))))


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention import flash_attention
    q = _sds((1, HEADS, 1024, HEAD_DIM), one_chip)
    _assert_mosaic(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False), q, q, q)


def test_mla_decode_compiles(one_chip):
    """DeepSeek-V3 absorbed decode at tp=4: 128/4 heads over the 512-wide
    latent with a 64-wide rope part, 8 slots x 1024 cached positions."""
    from repro.kernels.mla_decode import mla_decode_attention
    b, h, r, dr, s = 8, 128 // TP, 512, 64, 1024
    f32 = jnp.float32
    _assert_mosaic(
        lambda qe, qr, c, kr, n: mla_decode_attention(
            qe, qr, c, kr, n, scale=0.1, interpret=False),
        _sds((b, h, r), one_chip, f32), _sds((b, h, dr), one_chip, f32),
        _sds((b, s, r), one_chip), _sds((b, s, dr), one_chip),
        _sds((b,), one_chip, jnp.int32))


def test_matmul_compiles(one_chip):
    from repro.kernels import ops
    _assert_mosaic(lambda a, b: ops.matmul(a, b, interpret=False),
                   _sds((ROWS, D_MODEL), one_chip),
                   _sds((D_MODEL, D_FF), one_chip))
