"""Granite-4.0-H through the hybrid decoder (``models/nemotron_h.py`` read
from a ``granitemoehybrid`` file) against its plain reference
(``benchmark/references/granitemoehybrid.py``) at toy widths on the CPU:
logits, not tokens. float32 throughout, so the tolerance is that of two
orders of float32 summation, 2e-4 absolute on logits of magnitude ~1 (the
chunked scan sums a chunk's products where the reference walks positions;
the grouped product sums an expert's rows in another order), never a
rounding of bfloat16 size (4e-3 relative). Beside them: the share of the
experts ties to the uncut layer, the router's weights to a numpy reckoning,
the attention scale's default leaves every other decoder's attention as it
was, and a ``nemotron_h`` configuration traces no scalar it does not set."""

import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import loader
from eventgpt_tpu.config import HybridConfig, from_hf_config
from eventgpt_tpu.constants import EVENT_TOKEN_INDEX
from eventgpt_tpu.models import eventchat, experts, llama, nemotron_h as nh
from eventgpt_tpu.serve import ContinuousBatcher

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS") != "cpu", reason="a CPU test")

TOL = 2e-4
TOY = loader.read_json(os.path.join(loader.HERE, "configs",
                                    "granite-4.0-h-small-tiny.json"))
NEMOTRON = loader.read_json(os.path.join(loader.HERE, "configs",
                                         "nemotron3-super-tiny.json"))
REF = loader.reference_of(TOY)


def hf_of(**changes) -> dict:
    return {**TOY, **changes}


def params_of(hf: dict, seed: int = 0):
    """Seeded parameters with every leaf that the real initialiser sets to
    one or zero moved off it, so that a leaf left out or misplaced shows."""
    cfg = from_hf_config(hf, attn_impl="dense").llama
    params = nh.init_params(cfg, jax.random.PRNGKey(seed), jnp.float32)
    key = jax.random.PRNGKey(seed + 1)
    for layer in params["layers"]:
        for name in ("conv_b",):
            if name in layer:
                key, sub = jax.random.split(key)
                layer[name] = 0.1 * jax.random.normal(sub, layer[name].shape)
        for name in ("norm", "gate_norm", "D"):
            if name in layer:
                key, sub = jax.random.split(key)
                layer[name] = 1.0 + 0.2 * jax.random.normal(
                    sub, layer[name].shape)
    table = "embed_tokens" if "embed_tokens" in params else "lm_head"
    params[table] = params[table] * 50.0  # unit tables
    params["final_norm"] = 1.0 + 0.2 * jax.random.normal(
        key, params["final_norm"].shape)
    return cfg, params


def embeds(t: int, d: int, seed: int = 2):
    return jax.random.normal(jax.random.PRNGKey(seed), (t, d), jnp.float32)


def close(got, want, tol=TOL):
    err = float(jnp.max(jnp.abs(jnp.asarray(got) - jnp.asarray(want))))
    assert err <= tol, err


def test_from_hf_config_reads_the_published_keys():
    cfg = from_hf_config(TOY, attn_impl="dense").llama
    assert isinstance(cfg, HybridConfig)
    assert cfg.pattern == "MEMEMEMEME*EMEMEMEME" and cfg.num_layers == 20
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.experts_offset) == (
        16, 8, 8)
    assert (cfg.router, cfg.mlp_hidden_act, cfg.moe_latent_size) == (
        "softmax_topk", "silu", None)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (
        12.0, 0.22, 0.0078125, 16.0)
    assert cfg.tie_word_embeddings and cfg.resolved_head_dim() == 16
    assert (cfg.n_groups, cfg.ssm_state_size, cfg.conv_kernel,
            cfg.chunk_size) == (1, 16, 4, 16)
    assert (cfg.moe_intermediate_size,
            cfg.moe_shared_expert_intermediate_size) == (48, 96)
    # the cell's file: the first ten of the forty published layers
    full = loader.read_json(os.path.join(loader.HERE, "configs",
                                         "granite-4.0-h-small-event.json"))
    big = from_hf_config(full, attn_impl="dense").llama
    assert big.pattern == "MEMEMEMEME*EMEMEMEME"
    assert (big.n_routed_experts, big.experts_held, big.vocab_size) == (
        72, 36, 50176)
    assert big.mamba_inner == 8192 and big.conv_channels == 8448
    from eventgpt_tpu.config import event_chat_config_from_dict, to_dict

    whole = from_hf_config(TOY, attn_impl="dense")
    assert event_chat_config_from_dict(to_dict(whole)) == whole
    with pytest.raises(ValueError, match="num_hidden_layers"):
        from_hf_config(hf_of(num_hidden_layers=11), attn_impl="dense")


@pytest.mark.parametrize("key, value", [
    ("position_embedding_type", "rope"), ("mamba_proj_bias", True),
    ("mamba_conv_bias", False), ("attention_bias", True),
    ("hidden_act", "gelu"), ("normalization_function", "layernorm"),
    ("rope_scaling", {"type": "linear"}), ("mamba_expand", 4),
    ("shared_intermediate_size", 0), ("layer_types", ["mamba", "mlp"] * 5),
])
def test_what_is_not_implemented_is_refused_by_its_key(key, value):
    with pytest.raises(ValueError, match=key):
        from_hf_config(hf_of(**{key: value}), attn_impl="dense")


@pytest.mark.parametrize("types, t", [(["mamba"], 37), (["attention"], 37),
                                      (["attention"],
                                       nh.DENSE_EXPERTS_UP_TO + 22)])
def test_each_layer_alone(types, t):
    """A mixer and its expert sublayer, the experts in both forms of their
    product: a few tokens against every held expert, and more tokens sorted
    by expert."""
    hf = hf_of(layer_types=types, num_hidden_layers=1)
    cfg, params = params_of(hf)
    x = embeds(t, cfg.hidden_size)
    want = REF.decoder_logits(params, x, jnp.arange(t), hf)
    close(nh.forward(params, cfg, x[None])[0], want)


def test_prefill_then_decode_through_the_cache():
    """The toy's ten layers: a prompt prefilled, then one token at a time
    through the cache, against the reference's full forward pass."""
    cfg, params = params_of(TOY)
    p, steps = 37, 8
    x = embeds(p + steps, cfg.hidden_size)
    want = REF.decoder_logits(params, x, jnp.arange(p + steps), TOY)
    assert float(jnp.std(want)) > 0.1  # logits that can tell a fault
    cache = nh.init_cache(cfg, 1, 64, jnp.float32)
    mask = (jnp.arange(48) < p)[None]
    padded = jnp.pad(x[None, :p], ((0, 0), (0, 48 - p), (0, 0)))
    logits, cache = nh.prefill(params, cfg, padded, mask, cache,
                               last_only=True)
    close(logits[0], want[p - 1])
    for i in range(p, p + steps):
        logits, cache = nh.decode_step(params, cfg, x[None, i:i + 1], cache)
        close(logits[0], want[i])
    assert int(cache["length"][0]) == p + steps
    stats = np.asarray(cache["moe_stats"])
    assert stats.shape == (cfg.count("E"), len(nh.STATS)) == (10, 6)
    assert (stats[:, 3] == 1).all() and (stats[:, 2] <= 5).all()


def test_each_scalar_is_applied():
    """Each muP scalar, put back to the value that applies none, moves the
    logits of an attention layer and a mamba layer by ten times the
    tolerance or more: none of them is a no-op that the agreement with the
    reference could not see."""
    cfg, params = params_of(hf_of(layer_types=["attention", "mamba"],
                                  num_hidden_layers=2))
    x = embeds(20, cfg.hidden_size)
    base = nh.forward(params, cfg, x[None])[0]
    for field, none in (("embedding_multiplier", 1.0),
                        ("residual_multiplier", 1.0),
                        ("attention_multiplier", None),
                        ("logits_scaling", 1.0)):
        other = nh.forward(params, dataclasses.replace(cfg, **{field: none}),
                           x[None])[0]
        assert float(jnp.max(jnp.abs(other - base))) > 10 * TOL, field


def test_the_two_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """One expert sublayer with all 16 experts held, and the two shares of 8
    that the two chips of the deployment hold: the routed parts add up; the
    shared expert, which both chips compute alike, is counted once. The
    program and the reference, each against the uncut layer."""
    whole_hf = hf_of(layer_types=["mamba"], num_hidden_layers=1,
                     num_local_experts=16, experts_offset=0)
    cfg, params = params_of(whole_hf)
    layer = params["layers"][1]
    for t in (29, nh.DENSE_EXPERTS_UP_TO + 22):
        y = embeds(t, cfg.hidden_size)
        counted = jnp.ones((t,), bool)
        whole, stats = nh._moe_block(cfg, layer, y, counted, jnp.float32)
        assert int(stats[2]) == t * 5  # every assignment is held
        with jax.default_matmul_precision("highest"):
            shared = (jax.nn.silu(y @ layer["shared_gate"])
                      * (y @ layer["shared_up"])) @ layer["shared_down"]
        normed = y / jnp.sqrt((y * y).mean(-1, keepdims=True))
        whole_ref = REF._experts(
            {**layer, "norm": jnp.ones_like(layer["norm"])}, y, top_k=5,
            held=16, offset=0, residual=1.0, eps=0.0, lower=None) - y
        total, total_ref, held = shared, 0.0, 0
        for share in range(2):
            part_cfg = dataclasses.replace(cfg, experts_held=8,
                                           experts_offset=8 * share)
            part = {**layer, **{f"experts_{n}": layer[f"experts_{n}"][
                8 * share:8 * share + 8] for n in ("gate", "up", "down")}}
            out, st = nh._moe_block(part_cfg, part, y, counted, jnp.float32)
            total = total + (out - shared)
            held += int(st[2])
            total_ref = total_ref + REF._experts(
                {**part, "norm": jnp.ones_like(layer["norm"])}, y, top_k=5,
                held=8, offset=8 * share, residual=1.0, eps=0.0,
                lower=None) - y
            out_n, _ = nh._moe_block(part_cfg, part, normed, counted,
                                     jnp.float32)
            close(out_n, REF._experts(
                {**part, "norm": jnp.ones_like(layer["norm"])}, y, top_k=5,
                held=8, offset=8 * share, residual=1.0, eps=0.0,
                lower=None) - y)
        assert held == t * 5
        close(total, whole)
        # the reference's shares hold the shared expert twice: once too many
        with jax.default_matmul_precision("highest"):
            shared_n = (jax.nn.silu(normed @ layer["shared_gate"])
                        * (normed @ layer["shared_up"])) @ layer["shared_down"]
        close(total_ref - shared_n, whole_ref)


def test_the_softmax_over_the_top_k_logits_against_numpy():
    rng = np.random.default_rng(0)
    y = rng.standard_normal((7, 32)).astype(np.float32)
    router = rng.standard_normal((32, 12)).astype(np.float32)
    routing = experts.Routing(top_k=4, held=6, offset=3, normalise=False,
                              scale=1.0, form="softmax_topk")
    chosen, w = experts.route(routing, jnp.asarray(router), None,
                              jnp.asarray(y))
    logits = y.astype(np.float64) @ router.astype(np.float64)
    want = np.argsort(-logits, axis=-1)[:, :4]
    assert np.array_equal(np.asarray(chosen), want)
    top = np.take_along_axis(logits, want, axis=-1)
    e = np.exp(top - top.max(-1, keepdims=True))
    np.testing.assert_allclose(np.asarray(w), e / e.sum(-1, keepdims=True),
                               rtol=1e-5, atol=1e-6)
    assert np.allclose(np.asarray(w).sum(-1), 1.0)


def test_the_attention_scale_default_is_bit_for_bit():
    """``scale`` None is ``1 / sqrt(head_dim)`` computed as before: the dense
    read every decode step takes, the flash kernel (interpreted here) and
    the blocked kernel return what they returned, to the last bit; another
    scale is the scores times it."""
    from eventgpt_tpu.ops import flash_attention as fa

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    b, s, h, kvh, hd = 2, 40, 4, 2, 16
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kvh, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kvh, hd), jnp.float32)
    valid = jnp.arange(s)[None, :] < jnp.asarray([[40], [33]])
    causal = jnp.tril(jnp.ones((s, s), bool))
    mask = jnp.where(causal[None, None] & valid[:, None, None, :], 0.0,
                     jnp.finfo(jnp.float32).min)

    def old_grouped(q, k_full, v_full, mask):  # before ``scale`` existed
        qg = q.reshape(b, s, kvh, h // kvh, hd)
        sc = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k_full,
                        preferred_element_type=jnp.float32)
        sc = sc * (1.0 / math.sqrt(hd)) + mask[:, :, None]
        pr = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
        return jnp.einsum("bgrqk,bkgd->bqgrd", pr, v_full)

    got = jax.jit(llama.grouped_attention)(q, k, v, mask)
    assert bool(jnp.all(got == jax.jit(old_grouped)(q, k, v, mask)))
    assert bool(jnp.all(got == jax.jit(
        lambda *a: llama.grouped_attention(*a, 1.0 / math.sqrt(hd)))(
            q, k, v, mask)))
    rep = lambda x: jnp.repeat(x, h // kvh, axis=2)
    flash = fa.flash_attention(q, rep(k), rep(v), valid=valid)
    assert bool(jnp.all(flash == fa.flash_attention(
        q, rep(k), rep(v), valid=valid, scale=1.0 / math.sqrt(hd))))
    blocked = fa.flash_attention_blocked(q, k, v, valid, interpret=True)
    assert bool(jnp.all(blocked == fa.flash_attention_blocked(
        q, k, v, valid, interpret=True, scale=1.0 / math.sqrt(hd))))
    # another scale: the scores times it, in every form
    dense = llama.grouped_attention(q, k, v, mask, 1 / 128).reshape(b, s, h, hd)
    with jax.default_matmul_precision("highest"):
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, rep(k)) / 128 + mask
        want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), rep(v))
    keep = valid[:, :, None, None]
    close(jnp.where(keep, dense, 0), jnp.where(keep, want, 0), 1e-5)
    close(fa.flash_attention(q, rep(k), rep(v), valid=valid, scale=1 / 128),
          jnp.where(keep, want, 0), 1e-5)
    close(fa.flash_attention_blocked(q, k, v, valid, interpret=True,
                                     scale=1 / 128),
          jnp.where(keep, want, 0), 1e-5)


def test_a_nemotron_configuration_traces_no_scalar_it_does_not_set():
    """The hybrid's own configuration leaves every new field at its default:
    the stream's start, each block's residual add and the head trace the
    operations they traced before, and the expert blocks keep their leaves
    (latent, relu^2, sigmoid router with its bias)."""
    cfg = from_hf_config(NEMOTRON, attn_impl="dense").llama
    assert (cfg.router, cfg.mlp_hidden_act, cfg.tie_word_embeddings) == (
        "sigmoid", "relu2", False)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (1.0, 1.0, None,
                                                              1.0)
    x = jnp.ones((2, 3, cfg.hidden_size), jnp.bfloat16)
    prims = lambda f, *a: [e.primitive.name for e in
                           jax.make_jaxpr(f)(*a).jaxpr.eqns]
    assert prims(lambda x: nh._stream(cfg, x), x) == ["convert_element_type"]
    assert prims(lambda a, b: nh._residual(cfg, a, b), x, x) == ["add"]
    params = nh.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    assert "lm_head" in params
    h = jnp.ones((3, cfg.hidden_size), jnp.float32)
    assert str(jax.make_jaxpr(lambda p, h: nh._head(cfg, p, h))(params, h)) \
        == str(jax.make_jaxpr(llama._lm_head)(params, h))
    assert set(params["layers"][1]) == {
        "norm", "router", "e_score_correction_bias", "latent_down",
        "experts_up", "experts_down", "latent_up", "shared_up", "shared_down"}


def test_the_tied_head_is_one_leaf():
    """One table, stored as the head's leaf: a token's embedding is its
    column, so the end-of-sequence column that ``benchmark/weights.py``
    zeroes in a head is zero in the table too."""
    from eventgpt_tpu.models.synthetic import served_shapes
    from eventgpt_tpu.obs import memory

    cfg = from_hf_config(TOY, attn_impl="dense")
    shapes = served_shapes(cfg, jnp.bfloat16, "none", False)
    assert "embed_tokens" not in shapes["llama"]
    assert shapes["llama"]["lm_head"].shape == (64, 512)
    assert set(shapes["llama"]["layers"][1]) == {
        "norm", "router", "experts_gate", "experts_up", "experts_down",
        "shared_gate", "shared_up", "shared_down"}
    assert shapes["llama"]["layers"][1]["experts_gate"].shape == (8, 64, 48)
    untied = dataclasses.replace(
        cfg, llama=dataclasses.replace(cfg.llama, tie_word_embeddings=False))
    assert (memory.abstract_params_bytes(untied)
            - memory.abstract_params_bytes(cfg)) == 512 * 64 * 2
    params = eventchat.init_eventchat_params(cfg, jax.random.PRNGKey(0),
                                             jnp.float32)
    assert eventchat._vocab_size(params) == 512
    ids = jnp.asarray([[3, 500], [7, 2]])
    head = params["llama"]["lm_head"]
    assert bool(jnp.all(nh.embed_tokens(params["llama"], ids)
                        == jnp.stack([head[:, i] for i in ids.ravel()])
                        .reshape(2, 2, 64)))
    # the untied hybrid's lookup is the table's rows, as before
    untied = from_hf_config(NEMOTRON, attn_impl="dense").llama
    p = nh.init_params(untied, jax.random.PRNGKey(0), jnp.float32)
    assert bool(jnp.all(nh.embed_tokens(p, ids) == p["embed_tokens"][ids]))


# -- through ContinuousBatcher ---------------------------------------------------

def _model(seed: int = 0):
    """The toy with its decoder drawn as the benchmark draws it, by the
    configuration file's rules (``benchmark/weights.py``): under the
    initialiser's own deviations the tied head's greedy choice is the
    prompt's last id, repeated (the file's ``weights_why``)."""
    from benchmark import weights

    cfg = from_hf_config(TOY, attn_impl="dense")
    params = eventchat.init_eventchat_params(cfg, jax.random.PRNGKey(seed),
                                             jnp.float32)
    params["llama"] = weights.make_tree(
        jax.eval_shape(lambda k: nh.init_params(cfg.llama, k, jnp.float32),
                       jax.random.PRNGKey(0)), seed, rules=TOY["weights"])
    return cfg, params


def _request(rng, n_text: int):
    ids = ([1] + [int(i) for i in rng.integers(3, 200, n_text)]
           + [EVENT_TOKEN_INDEX] + [int(i) for i in rng.integers(3, 200, 5)])
    return ids, rng.standard_normal((5, 3, 28, 28)).astype(np.float32)


def test_generate_and_the_batcher_serve_it_alike():
    """The one-shot path and the continuous batcher, two requests in a wave
    of two rows, give each request the answer it gets alone."""
    cfg, params = _model()
    rng = np.random.default_rng(3)
    reqs = [_request(rng, n) for n in (8, 13)]
    alone = [eventchat.generate(params, cfg, [ids], px[None],
                                max_new_tokens=6, eos_token_id=None)[0]
             for ids, px in reqs]
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=4,
                            eos_token_id=None, prefix_cache=False)
    rids = [srv.submit(ids, px, 6) for ids, px in reqs]
    out = srv.run_until_drained()
    assert [out[r] for r in rids] == alone
    assert len(set(alone[0])) > 1  # the answer is not one id repeated


def test_the_counters_and_the_scopes_are_the_hybrids():
    """The ten expert blocks' counters leave with the prefill, the segment
    and its harvest, as the hybrid's do; the device scopes keep their names
    in the programs the batcher compiles."""
    from eventgpt_tpu.obs import trace as obs_trace

    cfg, params = _model()
    rng = np.random.default_rng(1)
    obs_trace.configure(4096)
    try:
        srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256,
                                chunk=3, eos_token_id=None,
                                prefix_cache=False)
        for n in (7, 9):
            srv.submit(*_request(rng, n), 7)
        srv.run_until_drained()
        ring = obs_trace.active().events()
    finally:
        obs_trace.disable()
    by_name = {}
    for e in ring:
        if e.get("ph") == "X" and "experts_touched" in (e.get("args") or {}):
            by_name.setdefault(e["name"], []).append(e["args"])
    assert set(by_name) == {"prefill", "dispatch", "harvest"}
    for args in by_name["dispatch"] + by_name["harvest"]:
        for name in ("experts_touched", "expert_fullest", "held_assignments",
                     "experts_over_capacity", "expert_tiles"):
            assert all(len(step) == 10 for step in args[name])
        for touched, held, tokens in zip(args["experts_touched"],
                                         args["held_assignments"],
                                         args["routed_tokens"]):
            assert all(0 <= t <= min(8, h) for t, h in zip(touched, held))
            assert all(h <= 5 * tokens for h in held)
    lc = cfg.llama
    x = jnp.ones((2, 24, lc.hidden_size), jnp.float32)
    cache = nh.init_cache(lc, 2, 32, jnp.float32)
    text = jax.jit(lambda p, x, c: nh.prefill(
        p, lc, x, jnp.ones((2, 24), bool), c)).lower(
        params["llama"], x, cache).as_text(debug_info=True)
    text += jax.jit(lambda p, x, c: nh.decode_step(p, lc, x, c)).lower(
        params["llama"], x[:, :1], cache).as_text(debug_info=True)
    for scope in ("ssm_scan", "ssm_step", "moe_route", "moe_experts",
                  "moe_shared", "prefill_attn", "decode_attn", "lm_head"):
        assert scope in text, scope


def test_a_wave_is_capped_by_its_sorted_rows():
    """A wave of full-width experts (4,096 wide, 10 a position) holds no
    more sorted rows than the hybrid's waves of 8,192 positions x 22 into a
    1,024-wide latent: 4,096 positions, four prompts of the cell's bucket;
    the hybrid's own cap is what it was."""
    full = from_hf_config(loader.read_json(os.path.join(
        loader.HERE, "configs", "granite-4.0-h-small-event.json")),
        attn_impl="dense").llama
    hybrid = from_hf_config(loader.read_json(os.path.join(
        loader.HERE, "configs", "nemotron3-super-120b-event.json")),
        attn_impl="dense").llama
    assert nh.wave_tokens(hybrid) == nh.WAVE_TOKENS == 8192
    assert nh.wave_tokens(full) == 4096
    assert nh.wave_tokens(from_hf_config(NEMOTRON, attn_impl="dense").llama) \
        == nh.wave_tokens(from_hf_config(TOY, attn_impl="dense").llama) == 8192
    cfg, params = _model()
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256,
                            eos_token_id=None, prefix_cache=False)
    assert srv._wave_tokens == 8192
