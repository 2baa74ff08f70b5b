"""The checkpoint-free builder (``models/synthetic.py``) against the real
init: one structure, and a model whose answer depends on its weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eventgpt_tpu.config import EventChatConfig
from eventgpt_tpu.models import eventchat, synthetic


def test_same_structure_as_the_real_init_and_its_constants():
    cfg = EventChatConfig.tiny()
    real = eventchat.init_eventchat_params(cfg, jax.random.PRNGKey(0),
                                           jnp.float32)
    syn = synthetic.random_eventchat_params(cfg, jnp.float32)
    assert (jax.tree_util.tree_structure(real)
            == jax.tree_util.tree_structure(syn))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(real)[0],
                            jax.tree_util.tree_leaves(syn)):
        name = jax.tree_util.keystr(path)
        assert isinstance(b, np.ndarray), name      # built on the host
        assert a.shape == b.shape and a.dtype == b.dtype, name
        a = np.asarray(a)
        if (a == 1).all():
            # A norm scale the name rules missed would silence a layer.
            assert (b == 1).all(), name
        elif not name.endswith("['bias']"):
            assert np.std(b) > 0, name              # random, not zeros
    again = synthetic.random_eventchat_params(cfg, jnp.float32)
    assert all((x == y).all() for x, y in zip(
        jax.tree_util.tree_leaves(syn), jax.tree_util.tree_leaves(again)))


@pytest.mark.parametrize("quant,fuse", [("int8", True), ("int8", False)])
def test_born_at_the_served_shapes(quant, fuse):
    """What prepare_model would produce with --quant/--fuse_params, so
    neither transform runs again (and the bf16 tree never exists)."""
    from eventgpt_tpu.models.llama import fuse_llama_params
    from eventgpt_tpu.ops.quant import quantize_llama_params

    cfg = EventChatConfig.tiny()
    syn = synthetic.random_eventchat_params(cfg, jnp.bfloat16, quant, fuse)
    want = eventchat.init_eventchat_params(cfg, jax.random.PRNGKey(0),
                                           jnp.bfloat16)["llama"]
    if fuse:
        want = fuse_llama_params(want)
    want = quantize_llama_params(want)
    got_s = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), syn["llama"])
    want_s = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), want)
    assert got_s == want_s


def test_quantized_and_plain_trees_carry_weights_of_one_scale():
    """int8 leaves dequantize to the variance the plain tree has: the
    serving matmul sees the same model either way."""
    from eventgpt_tpu.ops.quant import dequantize_tensor

    cfg = EventChatConfig.tiny()
    plain = synthetic.random_eventchat_params(cfg, jnp.float32)["llama"]
    quant = synthetic.random_eventchat_params(cfg, jnp.float32,
                                              "int8")["llama"]
    for group, name in (("attn", "q"), ("attn", "o"), ("mlp", "down")):
        w = plain["layers"][group][name]
        leaf = {k: jnp.asarray(v)
                for k, v in quant["layers"][group][name].items()}
        wq = np.asarray(dequantize_tensor(leaf))
        assert np.std(wq) == pytest.approx(np.std(w), rel=0.1), name


def test_load_model_serves_it_and_prepare_model_leaves_it_alone():
    """--model_path eventgpt-7b-random reaches the model through
    load_model -> prepare_model like any other start (tiny widths stand in
    for 7B here; chip_smoke.py runs the real ones)."""
    import types

    from eventgpt_tpu.cli import infer

    # A head wider than the byte tokenizer, as at 7B: no embedding resize.
    cfg = EventChatConfig.tiny(vocab_size=512)
    params = synthetic.random_eventchat_params(cfg, jnp.bfloat16, "int8")
    args = types.SimpleNamespace(
        model_path=synthetic.SYNTHETIC_7B, quant="int8", fuse_params=False,
        dtype="bfloat16", use_event_qformer=False, seed=0,
        spatial_temporal_encoder=None, pretrain_query_embedder=None,
        pretrain_attention_layers=None)
    from eventgpt_tpu.data.tokenizer import load_tokenizer

    cfg2, placed = infer.prepare_model(cfg, params, load_tokenizer("byte"),
                                       args)
    leaf = placed["llama"]["layers"]["attn"]["q"]
    assert leaf["q"].dtype == jnp.int8
    assert (np.asarray(leaf["q"])
            == params["llama"]["layers"]["attn"]["q"]["q"]).all()
    got_cfg, tok = infer.model_config_and_tokenizer(synthetic.SYNTHETIC_7B)
    assert got_cfg == EventChatConfig.eventgpt_7b()
    assert got_cfg.llama.attn_impl == "flash" and len(tok) >= 259
