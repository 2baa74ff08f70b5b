"""The plain reference, piece by piece against the program at toy widths on the
CPU (the test may import the program; the reference does not), and its lower
precisions against itself."""

import io
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correct, loader, reference, traffic, weights

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS") != "cpu", reason="a CPU test")


@pytest.fixture(scope="module")
def tiny():
    from eventgpt_tpu.config import from_hf_config
    from eventgpt_tpu.models.synthetic import served_shapes

    hf = loader.read_json(os.path.join(loader.HERE, "configs",
                                       "rehearsal-tiny.json"))
    cfg = from_hf_config(hf, attn_impl="dense")
    tree = weights.make_tree(served_shapes(cfg, jnp.float32, "int8", False), 7)
    return hf, cfg, tree


def stream(seed=3, n=3000):
    rng = np.random.default_rng(seed)
    return traffic.event_stream_npy(rng, {"events": n, "width": 64,
                                          "height": 48, "window_us": 50000})


def test_weights_are_seeded_and_shaped_as_served(tiny):
    from eventgpt_tpu.models.synthetic import served_shapes

    hf, cfg, tree = tiny
    shapes = served_shapes(cfg, jnp.float32, "int8", False)
    assert (jax.tree_util.tree_structure(tree)
            == jax.tree_util.tree_structure(shapes))
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(shapes)):
        assert a.shape == b.shape and a.dtype == b.dtype
    again = weights.make_tree(shapes, 7)
    other = weights.make_tree(shapes, 2**31 + 7)
    q = lambda t: np.asarray(t["llama"]["layers"]["attn"]["q"]["q"])
    assert (q(tree) == q(again)).all() and (q(tree) != q(other)).any()
    assert q(tree).dtype == np.int8 and q(tree).std() > 60
    assert (np.asarray(tree["llama"]["final_norm"]) == 1).all()
    assert (np.asarray(tree["clip"]["layers"]["attn"]["q"]["bias"]) == 0).all()
    assert np.asarray(tree["projector"]["mlp"][0]["kernel"]).std() > 0


@pytest.mark.parametrize("quant", ["int8", "none"])
def test_no_seed_ends_an_answer_early(tiny, quant):
    """The end-of-sequence id's column of the head is zero, as served and as
    the reference reads it, so its logit is 0 and it is never the first;
    every other column is what the seed drew."""
    from eventgpt_tpu.models.synthetic import served_shapes

    hf, cfg, _ = tiny
    eos = loader.id_tokenizer().eos_token_id
    shapes = served_shapes(cfg, jnp.float32, quant, False)
    plain = weights.make_tree(shapes, 2**31 + 11)
    tree = weights.make_tree(shapes, 2**31 + 11, never=(eos,))
    head = lambda t: np.asarray(reference._f32(t["llama"]["lm_head"]))
    got, drawn = head(tree), head(plain)
    assert got.shape == (cfg.llama.hidden_size, cfg.llama.vocab_size)
    assert (got[:, eos] == 0).all() and np.abs(drawn[:, eos]).max() > 0
    keep = np.arange(got.shape[1]) != eos
    np.testing.assert_array_equal(got[:, keep], drawn[:, keep])
    assert (np.abs(got[:, keep]).max(0) > 0).all()
    x = np.random.default_rng(0).standard_normal((64, got.shape[0]))
    assert (np.argmax(x @ got, -1) != eos).all()
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(plain)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_prompt_ids_are_the_programs():
    from eventgpt_tpu.data import prepare_event_prompt, tokenize_with_event
    from eventgpt_tpu.data.tokenizer import load_tokenizer

    tok = load_tokenizer("byte")
    tok.add_tokens(["<ev_patch>"], special_tokens=True)
    for q in traffic.questions({}):
        ids = tokenize_with_event(prepare_event_prompt(q), tok)
        pre, post = reference.prompt_ids(q)
        assert ids == list(pre) + [-200] + list(post)


def test_pixels_are_the_programs(tmp_path):
    from eventgpt_tpu.ops.image import process_event_file

    raw = stream()
    path = tmp_path / "ev.npy"
    path.write_bytes(raw)
    _, want = process_event_file(str(path), 5, 28)
    got = reference.pixels_from_npy(raw, 28)
    assert got.shape == (5, 3, 28, 28)
    np.testing.assert_array_equal(got, want)


def test_raster_last_event_wins():
    x = np.array([1, 1, 2], np.uint16)
    y = np.array([0, 0, 1], np.uint16)
    p = np.array([1, 0, 1], np.uint8)
    f = reference.raster(x, y, p)
    assert f.shape == (2, 3, 3)
    assert tuple(f[0, 1]) == (0, 0, 255)      # the later, polarity-0 event
    assert tuple(f[1, 2]) == (255, 0, 0) and tuple(f[0, 0]) == (255, 255, 255)


def test_event_tokens_are_the_programs(tiny):
    from eventgpt_tpu.models import eventchat

    hf, cfg, tree = tiny
    px = jnp.asarray(reference.pixels_from_npy(stream(), 28))
    want = eventchat.encode_events(tree, cfg, px)
    w = reference.widths_of(hf)
    got = reference.encode_events(tree["clip"], tree["projector"], px,
                                  heads=w["clip_heads"], patch=w["patch_size"])
    assert got.shape == (cfg.num_event_tokens, hf["hidden_size"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_logits_are_the_programs_full_forward(tiny):
    """GQA (4 / 2 heads), RoPE, SwiGLU, int8 leaves multiplied out."""
    from eventgpt_tpu.models import llama

    hf, cfg, tree = tiny
    rng = np.random.default_rng(0)
    emb = jnp.asarray(rng.standard_normal((40, hf["hidden_size"])), jnp.float32)
    want, _ = llama.prefill(
        tree["llama"], cfg.llama, emb[None], jnp.ones((1, 40), bool),
        llama.init_kv_cache(cfg.llama, 1, 64, dtype=jnp.float32))
    w = reference.widths_of(hf)
    got = reference.decoder_logits(
        tree["llama"], jnp.pad(emb, ((0, 24), (0, 0))), jnp.arange(40),
        heads=w["heads"], kv_heads=w["kv_heads"], theta=w["rope_theta"],
        eps=w["rms_norm_eps"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("lower", ["int4"])
def test_a_lower_precision_flips_tokens_the_reference_does_not(tiny, lower):
    """The control: put in the program's place, the lower precision puts
    tokens first that lie below the reference's best; float32 puts none."""
    hf, cfg, tree = tiny
    rng = np.random.default_rng(1)
    emb = jnp.asarray(rng.standard_normal((256, hf["hidden_size"])), jnp.float32)
    w = reference.widths_of(hf)
    kw = dict(heads=w["heads"], kv_heads=w["kv_heads"], theta=w["rope_theta"],
              eps=w["rms_norm_eps"])
    ref = np.asarray(reference.decoder_logits(tree["llama"], emb,
                                              jnp.arange(256), **kw))
    low = np.asarray(reference.decoder_logits(tree["llama"], emb,
                                              jnp.arange(256), lower=lower, **kw))
    same = correct.gaps_of(ref, ref.argmax(-1))
    gaps = correct.gaps_of(ref, low.argmax(-1))
    assert same.max() == 0.0
    assert gaps.max() > 0.0 and gaps.mean() > 1e-4
    assert (gaps >= 0).all()


def test_choose_sample_keeps_the_longest():
    fin = [{"rid": i, "tokens": list(range(3 + (i * 7) % 11))}
           for i in range(20)]
    a = correct.choose_sample(fin, 4, 5)
    assert a == correct.choose_sample(fin, 4, 5) and len(a) == 4
    assert max(len(f["tokens"]) for f in fin) == max(len(f["tokens"]) for f in a)
    assert len({f["rid"] for f in a}) == 4
    assert correct.choose_sample([], 4, 5) == []
    assert correct.choose_sample(fin, 4, 6) != a
