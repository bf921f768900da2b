"""Pallas kernel validation: interpret-mode sweep vs pure-jnp oracles."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.cam_match.cam_match import cam_match_pallas, cam_match_plan
from repro.kernels.cam_match.ref import cam_match_ref
from repro.kernels.rwkv6.ref import rwkv6_chunk_ref
from repro.kernels.rwkv6.rwkv6 import rwkv6_chunk_pallas


@pytest.mark.parametrize(
    "ncl,c,s,k,block_c",
    [
        (4, 16, 8, 32, 8),
        (2, 256, 64, 1024, 16),  # the chip's core geometry
        (3, 32, 16, 128, 16),
        (1, 64, 4, 64, 64),
        (5, 8, 8, 16, 4),
    ],
)
def test_cam_match_shapes(ncl, c, s, k, block_c):
    rng = np.random.default_rng(ncl * 1000 + c)
    n = ncl * c
    act = jnp.asarray(rng.random((ncl, k)), jnp.float32)
    tag = jnp.asarray(rng.integers(-1, k, (n, s)), jnp.int32)
    syn = jnp.asarray(rng.integers(0, 4, (n, s)), jnp.int32)
    out_k = cam_match_pallas(act, tag, syn, c, block_c=block_c)
    out_r = cam_match_ref(act, tag, syn, c)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cam_match_dtypes(dtype):
    rng = np.random.default_rng(0)
    act = jnp.asarray(rng.random((2, 64)), dtype)
    tag = jnp.asarray(rng.integers(-1, 64, (32, 8)), jnp.int32)
    syn = jnp.asarray(rng.integers(0, 4, (32, 8)), jnp.int32)
    out_k = cam_match_pallas(act, tag, syn, 16, block_c=8)
    out_r = cam_match_ref(act, tag, syn, 16)
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(out_r, np.float32), rtol=2e-2, atol=2e-2
    )


def test_cam_match_empty_cam_rows():
    """All-empty CAMs produce zero drive."""
    act = jnp.ones((2, 16), jnp.float32)
    tag = jnp.full((8, 4), -1, jnp.int32)
    syn = jnp.zeros((8, 4), jnp.int32)
    out = cam_match_pallas(act, tag, syn, 4, block_c=4)
    assert float(jnp.abs(out).max()) == 0.0


@pytest.mark.parametrize(
    "b,resident_halves", [(1, False), (130, False), (6, True)],
    ids=["one-slot", "several-blocks", "two-resident"],
)
def test_cam_match_slot_blocks_bit_identical(b, resident_halves):
    """Integer activities, as served (event counts x drive): the slot-blocked
    kernel equals the jnp reference exactly with one slot, with more slots
    than one block holds (several blocks and padded slots), and with half of
    every slot's clusters silent, as in a pool of two residents."""
    rng = np.random.default_rng(b)
    ncl, c, s, k = 4, 16, 8, 32
    n = ncl * c
    act = (rng.random((b, ncl, k)) < 0.3) * 8.0 * rng.integers(1, 5, (b, ncl, k))
    if resident_halves:  # slot i holds resident i % 2: its other half is silent
        for i in range(b):
            act[i, (1 - i % 2) * ncl // 2:(2 - i % 2) * ncl // 2] = 0.0
    act = jnp.asarray(act, jnp.float32)
    tag = jnp.asarray(rng.integers(-1, k, (n, s)), jnp.int32)
    syn = jnp.asarray(rng.integers(0, 4, (n, s)), jnp.int32)
    bb, b_pad, _ = cam_match_plan(b, k, s, 8)
    assert (bb, b_pad) == ((72, 144) if b == 130 else (b, b))
    out_k = cam_match_pallas(act, tag, syn, c, block_c=8)
    assert out_k.shape == (b, n, 4)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(cam_match_ref(act, tag, syn, c)))


@pytest.mark.parametrize(
    "b,t,h,p",
    [(2, 8, 3, 16), (1, 64, 2, 64), (2, 16, 4, 32), (1, 32, 1, 8)],
)
def test_rwkv6_chunk_shapes(b, t, h, p):
    rng = np.random.default_rng(b * 100 + t)
    r = jnp.asarray(rng.normal(size=(b, t, h, p)), jnp.float32) * 0.5
    k = jnp.asarray(rng.normal(size=(b, t, h, p)), jnp.float32) * 0.5
    v = jnp.asarray(rng.normal(size=(b, t, h, p)), jnp.float32) * 0.5
    lw = -jnp.asarray(rng.uniform(0.01, 1.0, size=(b, t, h, p)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(h, p)), jnp.float32) * 0.1
    s0 = jnp.asarray(rng.normal(size=(b, h, p, p)), jnp.float32) * 0.2
    y_k, s_k = rwkv6_chunk_pallas(r, k, v, lw, u, s0)
    y_r, s_r = rwkv6_chunk_ref(r, k, v, lw, u, s0)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), rtol=1e-4, atol=1e-5)


def test_rwkv6_chunk_state_threading():
    """Two chunks via the kernel == one double-length reference chunk."""
    rng = np.random.default_rng(5)
    b, t, h, p = 1, 8, 2, 16
    mk = lambda: jnp.asarray(rng.normal(size=(b, 2 * t, h, p)), jnp.float32) * 0.5
    r, k, v = mk(), mk(), mk()
    lw = -jnp.asarray(rng.uniform(0.01, 1.0, size=(b, 2 * t, h, p)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(h, p)), jnp.float32) * 0.1
    s0 = jnp.zeros((b, h, p, p), jnp.float32)
    y1, s1 = rwkv6_chunk_pallas(r[:, :t], k[:, :t], v[:, :t], lw[:, :t], u, s0)
    y2, s2 = rwkv6_chunk_pallas(r[:, t:], k[:, t:], v[:, t:], lw[:, t:], u, s1)
    y_ref, s_ref = rwkv6_chunk_ref(r, k, v, lw, u, s0)
    np.testing.assert_allclose(
        np.concatenate([y1, y2], 1), np.asarray(y_ref), rtol=1e-4, atol=1e-5
    )
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_ref), rtol=1e-4, atol=1e-5)
