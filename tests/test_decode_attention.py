"""Fused int8-KV decode-attention kernel: numerical parity.

The kernel itself is a measured NEGATIVE result for the product path
(r05 chip run: 10.1 ms vs 3.7 ms for the XLA fused-dequant attention at
7B shapes — decode attention inside the sequential layer scan is
op-granularity-bound, not dequant-bound), kept in-tree with the
measurement. These tests pin its correctness in interpreter mode so the
record stays reproducible.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eventgpt_tpu.ops.decode_attention import (
    decode_attention_int8,
    decode_attention_int8_paged,
    decode_attention_int8_paged_reference,
    decode_attention_int8_reference,
)


def _case(L=3, B=2, S=128, KV=4, G=2, hd=64, seed=0):
    rng = np.random.default_rng(seed)
    return (
        jnp.asarray(rng.normal(size=(B, KV, G, hd)), jnp.float32),
        jnp.asarray(rng.integers(-127, 128, (L, B, S, KV, hd)), jnp.int8),
        jnp.asarray(rng.uniform(0.001, 0.02, (L, B, S, KV, 1)), jnp.float32),
        jnp.asarray(rng.integers(-127, 128, (L, B, S, KV, hd)), jnp.int8),
        jnp.asarray(rng.uniform(0.001, 0.02, (L, B, S, KV, 1)), jnp.float32),
    )


@pytest.mark.parametrize("li", [0, 2])
def test_kernel_matches_reference(li):
    q, kq, ks, vq, vs = _case()
    nv = jnp.asarray([37, 100], jnp.int32)
    out = decode_attention_int8(q, kq, ks, vq, vs, li, nv)
    ref = decode_attention_int8_reference(q, kq, ks, vq, vs, li, nv)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2,  # bf16 post-dot-scaling vs f32 dequant
    )


def test_kernel_full_kv_block():
    # KV not divisible by 8 -> the whole axis rides one block.
    q, kq, ks, vq, vs = _case(KV=4, G=1)
    nv = jnp.asarray([5, 128], jnp.int32)
    out = decode_attention_int8(q, kq, ks, vq, vs, 1, nv)
    ref = decode_attention_int8_reference(q, kq, ks, vq, vs, 1, nv)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_kernel_mask_excludes_stale_slots():
    """Slots >= n_valid must not contribute: poisoning them changes nothing."""
    q, kq, ks, vq, vs = _case(B=1)
    nv = jnp.asarray([40], jnp.int32)
    out = decode_attention_int8(q, kq, ks, vq, vs, 0, nv)
    kq2 = kq.at[:, :, 40:].set(127)
    vs2 = vs.at[:, :, 40:].set(1e3)
    out2 = decode_attention_int8(q, kq2, ks, vq, vs2, 0, nv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2))


def test_kernel_multi_block_grid():
    """KV=16 -> block_kv=8, grid=(B, 2): exercises the hi block-offset maps
    (a wrong offset would corrupt heads 8..15 only at multi-block shapes)."""
    q, kq, ks, vq, vs = _case(KV=16, G=2, S=64, hd=32)
    nv = jnp.asarray([20, 64], jnp.int32)
    out = decode_attention_int8(q, kq, ks, vq, vs, 1, nv)
    ref = decode_attention_int8_reference(q, kq, ks, vq, vs, 1, nv)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2,
    )


# -- paged (block-table) variant (ISSUE 12) ---------------------------------


def _paged_case(L=2, B=3, N=9, bs=32, nbpr=4, KV=4, G=2, hd=32, seed=0):
    rng = np.random.default_rng(seed)
    return (
        jnp.asarray(rng.normal(size=(B, KV, G, hd)), jnp.float32),
        jnp.asarray(rng.integers(-127, 128, (L, N, bs, KV, hd)), jnp.int8),
        jnp.asarray(rng.uniform(0.001, 0.02, (L, N, bs, KV, 1)), jnp.float32),
        jnp.asarray(rng.integers(-127, 128, (L, N, bs, KV, hd)), jnp.int8),
        jnp.asarray(rng.uniform(0.001, 0.02, (L, N, bs, KV, 1)), jnp.float32),
        jnp.asarray(rng.integers(0, N, (B, nbpr)), jnp.int32),
    )


@pytest.mark.parametrize("li", [0, 1])
def test_paged_kernel_matches_reference(li):
    q, kq, ks, vq, vs, bt = _paged_case()
    nv = jnp.asarray([5, 67, 128], jnp.int32)
    out = decode_attention_int8_paged(q, kq, ks, vq, vs, li, bt, nv)
    ref = decode_attention_int8_paged_reference(q, kq, ks, vq, vs, li, bt,
                                                nv)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_paged_kernel_matches_dense_kernel_on_gathered_view():
    """The online-softmax block accumulation must agree with the dense
    kernel's one-shot softmax TIGHTLY (both run the same bf16 partial
    math; only the accumulation order differs) — this isolates the paged
    mechanics from the shared bf16-vs-f32 tolerance."""
    q, kq, ks, vq, vs, bt = _paged_case()
    nv = jnp.asarray([5, 67, 128], jnp.int32)
    out = decode_attention_int8_paged(q, kq, ks, vq, vs, 1, bt, nv)

    def flat(x):
        b, n, s = x.shape[0], x.shape[1], x.shape[2]
        return x.reshape((b, n * s) + x.shape[3:])

    gather = lambda buf: jnp.stack([flat(buf[li][bt])
                                    for li in range(buf.shape[0])])
    dense = decode_attention_int8(
        q, gather(kq), gather(ks), gather(vq), gather(vs), 1, nv)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(dense, np.float32),
        rtol=2e-3, atol=2e-3,
    )


def test_paged_kernel_masks_beyond_n_valid():
    """Blocks past a row's logical length must not contribute, even when
    its table points them at real (poisoned) pool blocks."""
    q, kq, ks, vq, vs, bt = _paged_case(B=1, nbpr=3)
    nv = jnp.asarray([40], jnp.int32)  # inside table slot 1 (bs=32)
    out = decode_attention_int8_paged(q, kq, ks, vq, vs, 0, bt, nv)
    poison_block = int(bt[0, 2])
    kq2 = kq.at[:, poison_block].set(127)
    vs2 = vs.at[:, poison_block].set(1e3)
    # Also poison the tail of the partially-valid block.
    kq2 = kq2.at[:, int(bt[0, 1]), 8:].set(127)
    out2 = decode_attention_int8_paged(q, kq2, ks, vq, vs2, 0, bt, nv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2))
