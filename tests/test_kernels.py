"""Per-kernel allclose sweeps against the ref.py oracles (interpret mode)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention


@pytest.mark.parametrize("m,k,n", [(256, 256, 256), (384, 640, 256),
                                   (128, 1024, 512), (512, 384, 128),
                                   (40, 100, 200)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_kernel(m, k, n, dtype):
    key = jax.random.PRNGKey(m + k + n)
    a = jax.random.normal(key, (m, k), dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n), dtype)
    out = kops.matmul(a, b, interpret=True)
    want = ref.matmul_ref(a, b).astype(dtype)
    tol = 1e-5 if dtype == jnp.float32 else 2e-1
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol * np.sqrt(k), rtol=tol)


# per-shard GEMMs of minicpm_2b at tp=4 (QKV, o-proj, FFN up, FFN down)
# and an odd shape
@pytest.mark.parametrize("m,k,n", [(512, 2304, 1728), (512, 576, 2304),
                                   (512, 2304, 1440), (512, 1440, 2304),
                                   (40, 100, 200)])
def test_plan_blocks_aligned(m, k, n):
    """The planned blocks are the blocks the wrappers run: each divides its
    padded dim, bm is a multiple of 16 and bk, bn of 128."""
    pm, pk, pn = kops.padded_dims(m, k, n)
    bm, bk, bn = kops.plan_blocks(m, k, n)
    assert (pm % bm, pk % bk, pn % bn) == (0, 0, 0)
    assert (bm % 16, bk % 128, bn % 128) == (0, 0, 0)
    assert kops.plan_blocks(512, 2048, 1024) == (256, 512, 256)


@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (2, 4, 2, 256, 64), (1, 8, 8, 512, 32), (1, 4, 1, 128, 64),
    (2, 2, 2, 384, 128),
])
def test_flash_attention(b, hq, hkv, s, d):
    q = jax.random.normal(jax.random.PRNGKey(1), (b, hq, s, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(2), (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(3), (b, hkv, s, d), jnp.float32)
    o = flash_attention(q, k, v, causal=True, bq=128, bkv=128, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_flash_attention_noncausal():
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 256, 64))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 256, 64))
    v = jax.random.normal(jax.random.PRNGKey(3), (1, 2, 256, 64))
    o = flash_attention(q, k, v, causal=False, bq=128, bkv=128,
                        interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


# ---------------------------------------------------------------------------
# distributed fused kernels: ring AG-GEMM / GEMM-RS on 4 virtual devices
# ---------------------------------------------------------------------------
_RING_TEST = r"""
import functools
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.compat import shard_map
from repro.kernels import ops as kops

mesh = Mesh(np.array(jax.devices()), ("tp",))
for (M, K, N, dtype, reverse) in [
        (512, 512, 512, jnp.float32, False),
        (512, 512, 512, jnp.float32, True),
        (1024, 256, 512, jnp.bfloat16, False),
        (512, 768, 1024, jnp.float32, False)]:
    A = jax.random.normal(jax.random.PRNGKey(0), (M, K), dtype)
    B = jax.random.normal(jax.random.PRNGKey(1), (K, N), dtype)

    @jax.jit
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P("tp", None), P(None, "tp")),
                       out_specs=P(None, "tp"), check_vma=False)
    def ag(a, b):
        return kops.ag_matmul_fused(a, b, axis_name="tp", reverse=%s)

    out = ag(A, B)
    want = jnp.dot(A.astype(jnp.float32), B.astype(jnp.float32))
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - want)))
    tol = 1e-3 * K**0.5 if dtype == jnp.float32 else 0.5 * K**0.5
    assert err < tol, ("ag", M, K, N, dtype, err)

    @jax.jit
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P(None, "tp"), P("tp", None)),
                       out_specs=P("tp", None), check_vma=False)
    def rs(a, b):
        return kops.matmul_rs_fused(a, b, axis_name="tp", reverse=%s)

    out = rs(A, B)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - want)))
    assert err < tol, ("rs", M, K, N, dtype, err)
print("RING_OK")
"""


@pytest.mark.parametrize("reverse", [False, True])
def test_fused_ring_kernels_4dev(subproc, reverse):
    out = subproc(_RING_TEST % (reverse, reverse), n_devices=4)
    assert "RING_OK" in out


# ---------------------------------------------------------------------------
# kernel tile-epilogue hook: act(AG(A)@B + bias) / act(RS(A@B) + bias)
# ---------------------------------------------------------------------------
_EPILOGUE_TEST = r"""
import functools
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.compat import shard_map
from repro.kernels import ops as kops

mesh = Mesh(np.array(jax.devices()), ("tp",))
M, K, N = 512, 256, 512
A = jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.float32)
B = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)
bias = jax.random.normal(jax.random.PRNGKey(2), (N,), jnp.float32) * 0.5
want = jnp.dot(A, B)
tol = 1e-3 * K**0.5

for act, bias_on in [("silu", True), ("sqrelu", False), (None, True)]:
    bb = bias if bias_on else None
    ref = want + (bias if bias_on else 0.0)
    if act == "silu":
        ref = jax.nn.silu(ref)
    elif act == "sqrelu":
        ref = jnp.square(jax.nn.relu(ref))

    @jax.jit
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P("tp", None), P(None, "tp"), P("tp")),
                       out_specs=P(None, "tp"), check_vma=False)
    def ag(a, b, bi):
        return kops.ag_matmul_fused(a, b, axis_name="tp", activation=act,
                                    bias=bi if bias_on else None)
    err = float(jnp.max(jnp.abs(ag(A, B, bias) - ref)))
    assert err < tol, ("ag", act, bias_on, err)

    @jax.jit
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P(None, "tp"), P("tp", None), P(None)),
                       out_specs=P("tp", None), check_vma=False)
    def rs(a, b, bi):
        return kops.matmul_rs_fused(a, b, axis_name="tp", activation=act,
                                    bias=bi if bias_on else None)
    err = float(jnp.max(jnp.abs(rs(A, B, bias) - ref)))
    assert err < tol, ("rs", act, bias_on, err)
print("EPILOGUE_OK")
"""


def test_kernel_tile_epilogue_4dev(subproc):
    """bias + activation in the fused kernels' tile epilogue match the
    unfused reference (RS: bias must be applied exactly once, AFTER the
    full cross-rank reduction)."""
    assert "EPILOGUE_OK" in subproc(_EPILOGUE_TEST, n_devices=4)


@pytest.mark.parametrize("b,h,r,dr,s,valid", [
    (2, 4, 64, 16, 256, 200), (1, 8, 128, 32, 512, 512),
    (2, 2, 32, 8, 128, 1),
])
def test_mla_decode_kernel(b, h, r, dr, s, valid):
    from repro.kernels.mla_decode import mla_decode_attention
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    qe = jax.random.normal(ks[0], (b, h, r), jnp.float32)
    qr = jax.random.normal(ks[1], (b, h, dr), jnp.float32)
    c = jax.random.normal(ks[2], (b, s, r), jnp.bfloat16)
    kr = jax.random.normal(ks[3], (b, s, dr), jnp.bfloat16)
    vl = jnp.asarray(valid, jnp.int32)
    out = mla_decode_attention(qe, qr, c, kr, vl, scale=0.1, bs=128,
                               interpret=True)
    want = ref.mla_decode_attention_ref(qe, qr, c, kr, vl, 0.1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_mla_decode_kernel_per_row_lengths():
    """Per-slot decode: each batch row masks at its OWN valid length."""
    from repro.kernels.mla_decode import mla_decode_attention
    b, h, r, dr, s = 3, 4, 64, 16, 256
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    qe = jax.random.normal(ks[0], (b, h, r), jnp.float32)
    qr = jax.random.normal(ks[1], (b, h, dr), jnp.float32)
    c = jax.random.normal(ks[2], (b, s, r), jnp.bfloat16)
    kr = jax.random.normal(ks[3], (b, s, dr), jnp.bfloat16)
    vl = jnp.asarray([17, 200, 256], jnp.int32)
    out = mla_decode_attention(qe, qr, c, kr, vl, scale=0.1, bs=128,
                               interpret=True)
    want = ref.mla_decode_attention_ref(qe, qr, c, kr, vl, 0.1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # row b must equal a single-row call at its own length
    for i in range(b):
        solo = mla_decode_attention(qe[i:i + 1], qr[i:i + 1], c[i:i + 1],
                                    kr[i:i + 1], vl[i:i + 1], scale=0.1,
                                    bs=128, interpret=True)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(solo[0]),
                                   atol=2e-5, rtol=2e-5)
