"""The dense attention branch contracts grouped queries against K / V as
stored (``models/llama._attn_block``). Held here to the form it replaced,
``_repeat_kv`` + per-head einsums, through every step function and cache
kind that reaches the branch, and to its point: the decode program holds no
value the size of a repeated cache layer."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eventgpt_tpu.config import LlamaConfig
from eventgpt_tpu.models import llama

KV, HD, S, T, BS = 2, 16, 32, 12, 8
LENGTHS = (12, 7, 3)  # ragged: right-padded prompts in one batch
B = len(LENGTHS)


def _cfg(rep: int, layers: int = 2) -> LlamaConfig:
    h = KV * rep
    return LlamaConfig(vocab_size=64, hidden_size=h * HD, intermediate_size=48,
                       num_layers=layers, num_heads=h, num_kv_heads=KV,
                       max_seq_len=64)


def _attn_block_repeat(cfg, q_proj, layer, cos, sin, k_full, v_full,
                       mask=None, **_):
    """The dense branch as it was: K / V repeated to the query's head count."""
    b, q_len, _ = q_proj.shape
    h, hd = cfg.num_heads, cfg.resolved_head_dim()
    q = llama.apply_rope(q_proj.reshape(b, q_len, h, hd), cos, sin)
    k = llama._repeat_kv(k_full, h // cfg.num_kv_heads)
    v = llama._repeat_kv(v_full, h // cfg.num_kv_heads)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (1.0 / math.sqrt(hd)) + mask
    probs = jax.nn.softmax(scores, axis=-1).astype(q_proj.dtype)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, q_len, h * hd)
    return llama._mm(ctx, layer["attn"]["o"])


def _paged(cache):
    """The same contents behind a shuffled block table (block 0 = scratch)."""
    nbpr = S // BS
    perm = np.random.default_rng(1).permutation(B * nbpr).reshape(B, nbpr)
    bt = jnp.asarray(1 + perm, jnp.int32)

    def pool(x):  # (L, B, S, ...) -> (L, 1 + B * nbpr, BS, ...)
        blocks = x.reshape(x.shape[0], B * nbpr, BS, *x.shape[3:])
        out = jnp.zeros((x.shape[0], 1 + B * nbpr) + blocks.shape[2:], x.dtype)
        return out.at[:, 1 + perm.reshape(-1)].set(blocks)

    return {"k": jax.tree.map(pool, cache["k"]),
            "v": jax.tree.map(pool, cache["v"]),
            "bt": bt, "length": cache["length"]}


def _setup(rep: int, dtype, kind: str):
    cfg = _cfg(rep)
    params = llama.init_llama_params(cfg, jax.random.PRNGKey(rep), dtype)
    x = jax.random.normal(jax.random.PRNGKey(7), (B, T, cfg.hidden_size), dtype)
    valid = jnp.arange(T)[None, :] < jnp.asarray(LENGTHS)[:, None]
    cache = llama.init_kv_cache(cfg, B, S, dtype,
                                quant=kind.startswith("int8"))
    return cfg, params, x, valid, cache


def _both(monkeypatch, fn):
    new = fn()
    monkeypatch.setattr(llama, "_attn_block", _attn_block_repeat)
    old = fn()
    monkeypatch.undo()
    return new, old


def _assert_same(new, old, dtype):
    # Same products, summed over the same axis: on this CPU the bf16 cases
    # agree bit for bit and the f32 cases to 2e-6; a wrong head order would
    # be wrong by the size of the logits.
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=tol, rtol=tol)


REPS = [1, 2, 4]
DTYPES = [pytest.param(jnp.bfloat16, id="bf16"), pytest.param(jnp.float32, id="f32")]
CACHES = ["bf16", "int8", "paged", "int8_paged"]


@pytest.mark.parametrize("kind", CACHES[:2])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("rep", REPS)
def test_prefill_grouped_equals_repeated(monkeypatch, rep, dtype, kind):
    cfg, params, x, valid, cache = _setup(rep, dtype, kind)
    new, old = _both(monkeypatch,
                     lambda: llama.prefill(params, cfg, x, valid, cache))
    _assert_same(new, old, dtype)
    assert np.isfinite(np.asarray(new[0], np.float32)).all()


@pytest.mark.parametrize("step", ["decode_step", "decode_kstep"])
@pytest.mark.parametrize("kind", CACHES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("rep", REPS)
def test_decode_grouped_equals_repeated(monkeypatch, rep, dtype, kind, step):
    cfg, params, x, valid, cache = _setup(rep, dtype, kind)
    _, cache = llama.prefill(params, cfg, x, valid, cache)
    if kind.endswith("paged"):
        cache = _paged(cache)
    q_len = 1 if step == "decode_step" else 4
    tok = jax.random.normal(jax.random.PRNGKey(9),
                            (B, q_len, cfg.hidden_size), dtype)
    new, old = _both(monkeypatch,
                     lambda: getattr(llama, step)(params, cfg, tok, cache))
    _assert_same(new, old, dtype)
    logits = np.asarray(new[0], np.float32)
    assert np.isfinite(logits).all()
    assert (np.asarray(new[1]["length"]) == np.asarray(LENGTHS) + q_len).all()


def test_paged_view_equals_dense():
    """The helper's pool holds what the dense cache holds (or the paged
    cases above compare two wrong answers)."""
    cfg, params, x, valid, cache = _setup(2, jnp.float32, "paged")
    _, cache = llama.prefill(params, cfg, x, valid, cache)
    paged = _paged(cache)
    view = llama._cache_read_layer(paged["k"], 1, jnp.float32, False,
                                   bt=paged["bt"])
    np.testing.assert_array_equal(np.asarray(view), np.asarray(cache["k"][1]))


def _values(jaxpr, out):
    """(sorted non-unit dims, dtype, primitive) of every value computed."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            if hasattr(v.aval, "shape"):
                dims = tuple(sorted(d for d in v.aval.shape if d != 1))
                out.append((dims, v.aval.dtype, eqn.primitive.name))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _values(sub, out)
    return out


@pytest.mark.parametrize("step,q_len", [("decode_step", 1), ("decode_kstep", 4)])
@pytest.mark.parametrize("rep", [2, 4])
def test_decode_jaxpr_holds_no_repeated_cache_layer(rep, step, q_len):
    """No chip needed: a GQA decode step computes no value of shape
    (B, S, KV, rep, hd) or (B, S, H, hd) (the repeat), in any order of axes,
    and none of (B, S, KV, hd) in f32 from a bf16 cache (the copy that the
    per-head form made XLA write)."""
    cfg = _cfg(rep, layers=5)  # the stacked cache is no (.., rep, ..) shape
    params = llama.init_llama_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
    cache = llama.init_kv_cache(cfg, B, S, jnp.bfloat16)
    tok = jnp.zeros((B, q_len, cfg.hidden_size), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda p, t, c: getattr(llama, step)(p, cfg, t, c))(params, tok, cache)
    values = _values(jaxpr.jaxpr, [])
    layer = tuple(sorted((B, S, KV, HD)))
    repeated = {tuple(sorted((B, S, KV, rep, HD))),
                tuple(sorted((B, S, KV * rep, HD)))}
    assert not [v for v in values if v[0] in repeated]
    assert not [v for v in values if v[0] == layer and v[1] == jnp.float32]
    # the guard looks into the scan's body: the layer's read is among the values
    assert [v for v in values if v[0] == layer and v[1] == jnp.bfloat16]
