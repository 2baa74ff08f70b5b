"""Flash attention kernel parity vs dense reference (interpret mode on CPU)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eventgpt_tpu.config import LlamaConfig
from eventgpt_tpu.models import llama as llama_mod
from eventgpt_tpu.ops.flash_attention import flash_attention
from eventgpt_tpu.parallel.ring import dense_reference_attention


@pytest.mark.parametrize("shape,causal", [
    ((2, 128, 2, 128), True),
    ((1, 256, 4, 128), True),
    ((2, 128, 2, 128), False),
])
def test_flash_matches_dense(shape, causal):
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.float32) for _ in range(3))
    ref = dense_reference_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)


def test_flash_padding_mask():
    rng = np.random.default_rng(1)
    b, s, h, hd = 2, 128, 2, 128
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32) for _ in range(3))
    lens = np.array([100, 128])
    valid = jnp.asarray(np.arange(s)[None, :] < lens[:, None])
    ref = dense_reference_attention(q, k, v, valid=valid, causal=True)
    out = flash_attention(q, k, v, valid=valid, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)
    # Padded query rows zero.
    assert np.abs(np.asarray(out[0, 100:])).max() == 0.0


def test_flash_unaligned_seq_len():
    """S not a block multiple: internal padding must not change results."""
    rng = np.random.default_rng(2)
    b, s, h, hd = 1, 200, 2, 128
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32) for _ in range(3))
    ref = dense_reference_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True)
    assert out.shape == (b, s, h, hd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)


def test_llama_prefill_flash_matches_dense():
    cfg_dense = LlamaConfig(
        vocab_size=64, hidden_size=256, intermediate_size=256, num_layers=2,
        num_heads=2, num_kv_heads=1, head_dim=128, max_seq_len=256,
    )
    cfg_flash = dataclasses.replace(cfg_dense, attn_impl="flash")
    params = llama_mod.init_llama_params(cfg_dense, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    b, t = 2, 130  # deliberately unaligned
    embeds = jnp.asarray(rng.normal(size=(b, t, cfg_dense.hidden_size)) * 0.1, jnp.float32)
    mask = jnp.asarray(np.arange(t)[None, :] < np.array([[t], [100]])[:, 0:1])

    ref = llama_mod.forward(params, cfg_dense, embeds, mask)
    out = llama_mod.forward(params, cfg_flash, embeds, mask)
    # Compare only real (non-pad) positions; pad rows differ by construction
    # (dense mask zeroes columns, flash zeroes padded query rows).
    m = np.asarray(mask)
    np.testing.assert_allclose(
        np.asarray(out)[m], np.asarray(ref)[m], atol=5e-4, rtol=5e-3
    )


def test_flash_mismatched_block_sizes():
    """block_q/block_k where neither divides the other must still cover all keys."""
    rng = np.random.default_rng(4)
    b, s, h, hd = 1, 200, 2, 128
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32) for _ in range(3))
    ref = dense_reference_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=96)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)


def test_flash_gradients_match_dense():
    rng = np.random.default_rng(5)
    b, s, h, hd = 1, 128, 2, 128
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, h, hd)) * 0.3, jnp.float32) for _ in range(3))
    lens = np.array([100])
    valid = jnp.asarray(np.arange(s)[None, :] < lens[:, None])

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, valid=valid, causal=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_reference_attention(q, k, v, valid=valid, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4, rtol=1e-3)


def test_llama_train_forward_with_flash_differentiable():
    cfg = dataclasses.replace(
        LlamaConfig(vocab_size=64, hidden_size=256, intermediate_size=256,
                    num_layers=1, num_heads=2, num_kv_heads=2, head_dim=128,
                    max_seq_len=128),
        attn_impl="flash",
    )
    params = llama_mod.init_llama_params(cfg, jax.random.PRNGKey(0))
    embeds = jnp.asarray(
        np.random.default_rng(6).normal(size=(1, 128, 256)) * 0.1, jnp.float32
    )

    def loss(p):
        return jnp.mean(llama_mod.forward(p, cfg, embeds) ** 2)

    g = jax.grad(loss)(params)
    gn = sum(float(jnp.sum(jnp.abs(x))) for x in jax.tree_util.tree_leaves(g))
    assert np.isfinite(gn) and gn > 0


# -- the blocked kernel: K / V through the grid, and the band ---------------------

def _banded_reference(q, k, v, valid, window):
    """Dense attention under the band ``0 <= i - j < window`` (None: causal),
    K / V at their own head count."""
    b, s, h, hd = q.shape
    rep = h // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    see = j <= i
    if window is not None:
        see = see & (i - j < window)
    see = see[None, None] & valid[:, None, None, :]
    pr = jax.nn.softmax(jnp.where(see, sc, -1e30), -1)
    out = jnp.einsum("bhqk,bkhd->bqhd", pr, v)
    return jnp.where(valid[:, :, None, None], out, 0)


# A row's keys: an int is a length (right padding); a pair (a, b) is every
# position but [a, b) (a hole, or at 0 left padding).
BLOCKED_CASES = [
    (700, 300, [700, 333]),      # a window smaller than the sequence, ragged
    (512, 512, [512, 512]),      # equal to it
    (600, 5000, [600, 555]),     # larger: the whole causal square
    (1536, 512, [1536, 1]),      # a band of whole blocks; a row of one position
    (1100, 129, [1024, 1100]),   # a window off the block grain
    (200, None, [150, 200]),     # no window, blocked K / V
    (1536, None, [(200, 260), (0, 300)]),  # not right-padded: a hole, the left
]


def _blocked_inputs(s, lens, dtype=jnp.float32, h=4, kvh=2):
    rng = np.random.default_rng(7)
    b, hd = len(lens), 128
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, kvh, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, kvh, hd)), dtype)
    pos = np.arange(s)
    valid = np.stack([pos < n if isinstance(n, int)
                      else (pos < n[0]) | (pos >= n[1]) for n in lens])
    return q, k, v, jnp.asarray(valid)


@pytest.mark.parametrize("s, window, lens", BLOCKED_CASES)
def test_blocked_flash_matches_the_banded_reference(s, window, lens):
    from eventgpt_tpu.ops.flash_attention import flash_attention_blocked

    q, k, v, valid = _blocked_inputs(s, lens)
    out = flash_attention_blocked(q, k, v, valid, window=window,
                                  interpret=True)
    ref = _banded_reference(q, k, v, valid, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)
    assert np.abs(np.asarray(out)[~np.asarray(valid)]).max(initial=0.0) == 0.0


def _census_by_pairs(valid, window, block):
    """(computed, interior) by brute force: every (query block, key block)
    step the kernel's grid computes, interior where no pair of it is
    masked; summed over ``valid``'s rows (B, positions) bool."""
    from eventgpt_tpu.ops.flash_attention import _geometry

    block, s_pad, window = _geometry(valid.shape[1], window, block)
    keys = np.zeros((valid.shape[0], s_pad), bool)
    keys[:, :valid.shape[1]] = valid
    i = np.arange(block)[:, None]
    j = np.arange(block)[None, :]
    computed = interior = 0
    for row in keys:
        for qb in range(s_pad // block):
            qs = qb * block
            first = 0 if window is None else max(qs - window + 1, 0) // block
            for kb in range(first, (qs + block - 1) // block + 1):
                d = (qs + i) - (kb * block + j)
                seen = (d >= 0) & row[kb * block:(kb + 1) * block][None, :]
                if window is not None:
                    seen &= d < window
                computed += 1
                interior += bool(seen.all())
    return computed, interior


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s, window, lens", BLOCKED_CASES)
def test_interior_blocks_are_the_masked_body_bit_for_bit(s, window, lens,
                                                         dtype, monkeypatch):
    """Blocks of 128, so that most of a call's blocks are interior: the
    kernel as shipped against the kernel with every block classified as
    needing its mask (the helper patched), in one trace; equal bit for
    bit, and at float32 the band's reference all the same. Two query heads
    on one K / V head, for the suite's time."""
    from eventgpt_tpu.ops import flash_attention as fa

    q, k, v, valid = _blocked_inputs(s, lens, dtype, h=2, kvh=1)
    forward = fa._flash_blocked_forward.__wrapped__

    def both(q, k, v, valid):
        shipped = forward(q, k, v, valid, window, 128, True)
        with monkeypatch.context() as mp:
            mp.setattr(fa, "_interior", lambda *args: False)
            masked = forward(q, k, v, valid, window, 128, True)
        return shipped, masked

    shipped, masked = map(np.asarray, jax.jit(both)(q, k, v, valid))
    assert np.array_equal(shipped, masked)
    # Every case but the window of 129 (no block of 128 fits inside it) has
    # interior blocks, so the comparison is not of one body with itself.
    assert (_census_by_pairs(np.asarray(valid), window, 128)[1] > 0
            or window < 2 * 128 - 1)
    if dtype == jnp.float32:
        np.testing.assert_allclose(
            shipped, np.asarray(_banded_reference(q, k, v, valid, window)),
            atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("s, window, lens, block", [
    (s, w, lens, block) for s, w, lens in BLOCKED_CASES
    if all(isinstance(n, int) for n in lens) for block in (128, 1024)
] + [(12288, 4096, [12280], 1024), (12288, None, [12280], 1024)])
def test_the_census_counts_the_blocks_the_kernel_leaves_unmasked(
        s, window, lens, block):
    """``flash_block_census`` against every pair of every block the grid
    computes; Trinity's prompt (12,280 in the 12,288 bucket, a window of
    4,096) is 50 blocks a head banded, 30 of them interior, and 78 whole,
    66 interior."""
    from eventgpt_tpu.ops.flash_attention import flash_block_census

    valid = np.arange(s)[None, :] < np.array(lens)[:, None]
    got = flash_block_census(s, window, lens, block=block)
    assert got == _census_by_pairs(valid, window, block)
    if s == 12288:
        assert got == ((50, 30) if window else (78, 66))


def test_the_band_is_guarded_and_named():
    """A window changes the result (the mask is not decoration), and the
    banded call carries its own name on a trace while the full causal call
    keeps ``flash_forward``, which ``flash_roofline`` prices as a square."""
    from eventgpt_tpu.ops import flash_attention as fa

    rng = np.random.default_rng(8)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 384, 2, 128)), jnp.float32)
               for _ in range(3))
    valid = jnp.ones((1, 384), bool)
    banded = fa.flash_attention_blocked(q, k, v, valid, window=100,
                                        interpret=True)
    whole = fa.flash_attention_blocked(q, k, v, valid, interpret=True)
    assert np.abs(np.asarray(banded[0, :100] - whole[0, :100])).max() < 1e-5
    assert np.abs(np.asarray(banded[0, 100:] - whole[0, 100:])).max() > 1e-2
    traced = lambda w: str(jax.make_jaxpr(
        lambda q, k, v: fa._flash_blocked_forward(
            q, k, v, valid, window=w, interpret=True))(q, k, v))
    assert "name=flash_window_forward" in traced(100)
    for w in (None, 4096):  # no window, and one that covers the sequence
        assert "name=flash_forward" in traced(w)
        assert "flash_window_forward" not in traced(w)


def test_afmoe_prefill_flash_matches_dense():
    """The window / global decoder's prefill through the blocked kernel
    against its dense masks, both kinds of layer, a ragged wave."""
    from eventgpt_tpu.config import AfmoeConfig
    from eventgpt_tpu.models import afmoe

    cfg = AfmoeConfig(
        layer_types=("sliding_attention", "full_attention"), sliding_window=96,
        num_dense_layers=1, vocab_size=64, hidden_size=128,
        intermediate_size=128, moe_intermediate_size=64, num_heads=2,
        num_kv_heads=1, head_dim=128, num_experts=8, experts_held=8,
        num_experts_per_tok=2, route_scale=2.0, max_seq_len=512)
    params = afmoe.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(9)
    b, t = 2, 256
    embeds = jnp.asarray(rng.normal(size=(b, t, 128)) * 0.1, jnp.float32)
    mask = jnp.asarray(np.arange(t)[None, :] < np.array([[t], [170]]))
    ref = afmoe.forward(params, cfg, embeds, mask)
    out = afmoe.forward(params, dataclasses.replace(cfg, attn_impl="flash"),
                        embeds, mask)
    m = np.asarray(mask)
    np.testing.assert_allclose(np.asarray(out)[m], np.asarray(ref)[m],
                               atol=5e-4, rtol=5e-3)
