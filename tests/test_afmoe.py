"""The window / global decoder (``models/afmoe.py``) against its plain
reference (``benchmark/references/afmoe.py``) at toy widths on the CPU:
logits, not tokens. float32 throughout, so the tolerance is that of two
orders of float32 summation: 2e-4 absolute on logits of magnitude ~3 (the
grouped product sums an expert's rows in another order, the ring holds the
keys in another order than the reference's positions), never a rounding of
bfloat16 size (4e-3 relative). The reference with its window switched off,
and the reference in int8, both miss it by orders of magnitude
(``test_prefill_then_decode_through_the_ring``)."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import loader
from eventgpt_tpu.config import AfmoeConfig, HybridConfig, from_hf_config
from eventgpt_tpu.constants import EVENT_TOKEN_INDEX
from eventgpt_tpu.models import afmoe, eventchat, experts as experts_mod
from eventgpt_tpu.serve import ContinuousBatcher

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS") != "cpu", reason="a CPU test")

TOL = 2e-4
TOY = loader.read_json(os.path.join(loader.HERE, "configs",
                                    "trinity-large-tiny.json"))
REF = loader.reference_of(TOY)
W = TOY["sliding_window"]


def hf_of(**changes) -> dict:
    return {**TOY, **changes}


def params_of(hf: dict, seed: int = 0):
    """Seeded parameters with every leaf that the real initialiser sets to
    one or zero moved off it, so that a leaf left out or misplaced shows."""
    cfg = from_hf_config(hf, attn_impl="dense").llama
    params = afmoe.init_params(cfg, jax.random.PRNGKey(seed), jnp.float32)
    key = jax.random.PRNGKey(seed + 1)
    for layer in params["layers"]:
        for name, leaf in layer.items():
            key, sub = jax.random.split(key)
            if name.endswith("norm"):
                layer[name] = 1.0 + 0.2 * jax.random.normal(sub, leaf.shape)
            elif name == "expert_bias":
                layer[name] = 0.1 * jax.random.normal(sub, leaf.shape)
    params["embed_tokens"] = params["embed_tokens"] * (50.0 / 8.0)
    return cfg, params


def embeds(t: int, d: int, seed: int = 2):
    return jax.random.normal(jax.random.PRNGKey(seed), (t, d),
                             jnp.float32) / 8.0


def close(got, want, tol=TOL):
    err = float(jnp.max(jnp.abs(jnp.asarray(got) - jnp.asarray(want))))
    assert err <= tol, err


def test_from_hf_config_reads_the_published_keys():
    cfg = from_hf_config(TOY, attn_impl="dense").llama
    assert isinstance(cfg, AfmoeConfig)
    assert cfg.layer_types == ("sliding_attention",) * 4 + ("full_attention",)
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_offset) == (32, 8, 8)
    assert (cfg.sliding_window, cfg.num_dense_layers) == (16, 1)
    assert (cfg.route_scale, cfg.route_norm, cfg.mup_enabled) == (2.448, True, True)
    assert cfg.max_seq_len == TOY["max_position_embeddings"]
    # the cell's file: the published widths, the cut by layers_kept
    big_hf = loader.read_json(os.path.join(loader.HERE, "configs",
                                           "trinity-large-event.json"))
    big = from_hf_config(big_hf, attn_impl="dense").llama
    assert len(big_hf["layer_types"]) == 60
    assert big.layer_types == cfg.layer_types
    assert (big.hidden_size, big.num_heads, big.num_kv_heads, big.head_dim,
            big.intermediate_size, big.moe_intermediate_size,
            big.num_experts_per_tok, big.sliding_window, big.num_experts,
            big.experts_held, big.vocab_size) == (
        3072, 48, 8, 128, 12288, 3072, 4, 4096, 256, 32, 25024)
    assert big.max_seq_len == 262144  # no 4,096 cap
    with pytest.raises(ValueError, match="num_hidden_layers"):
        from_hf_config(hf_of(layers_kept=[0, 4]), attn_impl="dense")
    short = from_hf_config(hf_of(num_hidden_layers=2, layers_kept=[0, 4]),
                           attn_impl="dense").llama
    assert short.layer_types == ("sliding_attention", "full_attention")
    with pytest.raises(ValueError, match="score_func"):
        from_hf_config(hf_of(score_func="softmax"), attn_impl="dense")
    # the other kinds of file still build their own decoders
    hybrid = loader.read_json(os.path.join(loader.HERE, "configs",
                                           "nemotron3-super-tiny.json"))
    assert isinstance(from_hf_config(hybrid, attn_impl="dense").llama,
                      HybridConfig)
    # and the configuration survives its own serialisation
    from eventgpt_tpu.config import event_chat_config_from_dict, to_dict

    whole = from_hf_config(TOY, attn_impl="dense")
    assert event_chat_config_from_dict(to_dict(whole)) == whole


@pytest.mark.parametrize("types, dense, t", [
    (["sliding_attention"], 1, 53), (["full_attention"], 1, 53),
    (["sliding_attention"], 0, 37), (["full_attention"], 0, 37)])
def test_each_kind_of_layer_alone(types, dense, t):
    hf = hf_of(layer_types=types, num_hidden_layers=1, num_dense_layers=dense)
    cfg, params = params_of(hf)
    x = embeds(t, cfg.hidden_size)
    got = afmoe.forward(params, cfg, x[None])[0]
    close(got, REF.decoder_logits(params, x, jnp.arange(t), hf))


def _through_the_cache(cfg, params, x, t: int, max_len: int = 128):
    """Prefill ``t`` positions (padded to a bucket of 64), then decode the
    rest one token a step: logits at every position from ``t - 1`` on."""
    bucket = 64
    cache = afmoe.init_cache(cfg, 1, bucket, dtype=jnp.float32)
    xin = jnp.pad(x[None, :t], ((0, 0), (0, bucket - t), (0, 0)))
    mask = (jnp.arange(bucket) < t)[None]
    logits, wave = afmoe.prefill(params, cfg, xin, mask, cache, last_only=True)
    cache = afmoe.init_cache(cfg, 1, max_len, dtype=jnp.float32)
    cache = {**cache, **{n: wave[n] for n in afmoe.fixed_state(cfg)},
             "k": cache["k"].at[:, :, :bucket].set(wave["k"]),
             "v": cache["v"].at[:, :, :bucket].set(wave["v"]),
             "length": wave["length"]}
    step = jax.jit(lambda e, c: afmoe.decode_step(params, cfg, e, c))
    out = [logits[0]]
    for i in range(t, x.shape[0]):
        lg, cache = step(x[None, i:i + 1], cache)
        out.append(lg[0])
    return jnp.stack(out), cache


def test_prefill_then_decode_through_the_ring():
    """A prompt of 53 positions (the ring of 16 has wrapped three times) and
    40 decode steps (it wraps twice more), against the reference's full
    forward pass; the same comparison fails by orders of magnitude against
    the reference with its window switched off and against the int8
    control, so the tolerance guards the mask and the precision."""
    cfg, params = params_of(TOY)
    t, n = 53, 40
    x = embeds(t + n, cfg.hidden_size)
    got, cache = _through_the_cache(cfg, params, x, t)
    rows = jnp.arange(t - 1, t + n)
    want = REF.decoder_logits(params, x, rows, TOY)
    close(got, want)
    assert int(cache["length"][0]) == t + n
    assert cache["k_ring3"].shape == (1, 1, W, 2, 16)
    assert cache["k"].shape[2] == 128
    unwindowed = REF.decoder_logits(params, x, rows,
                                    hf_of(sliding_window=1 << 20))
    assert float(jnp.max(jnp.abs(unwindowed - want))) > 100 * TOL
    control = REF.decoder_logits(params, x, rows, TOY, lower="int8")
    assert float(jnp.max(jnp.abs(control - want))) > 10 * TOL


def test_a_prompt_shorter_than_the_window_masks_the_empty_slots():
    cfg, params = params_of(hf_of(sliding_window=48))
    hf = hf_of(sliding_window=48)
    t, n = 21, 40  # the ring fills at position 48, during decode
    x = embeds(t + n, cfg.hidden_size, seed=5)
    got, _ = _through_the_cache(cfg, params, x, t)
    close(got, REF.decoder_logits(params, x, jnp.arange(t - 1, t + n), hf))


def test_a_right_padded_wave_against_each_row_alone():
    """Three rows of unequal length in one bucket, one of them shorter than
    the window: logits, rings and one decode step are each row's own."""
    cfg, params = params_of(TOY)
    lens = [53, 37, 9]
    bucket = 64
    rows = jnp.stack([embeds(bucket, cfg.hidden_size, seed=10 + i)
                      for i in range(3)])
    mask = jnp.asarray(np.arange(bucket)[None, :] < np.array(lens)[:, None])
    cache = afmoe.init_cache(cfg, 3, bucket, dtype=jnp.float32)
    logits, cache = afmoe.prefill(params, cfg, rows, mask, cache,
                                  last_only=True)
    nxt = embeds(1, cfg.hidden_size, seed=20)
    # one more slot in the plane for the step's key
    cache = {**cache, "k": jnp.pad(cache["k"], ((0, 0), (0, 0), (0, 64),
                                                (0, 0), (0, 0))),
             "v": jnp.pad(cache["v"], ((0, 0), (0, 0), (0, 64), (0, 0),
                                       (0, 0)))}
    step, _ = afmoe.decode_step(params, cfg, jnp.broadcast_to(
        nxt[None], (3, 1, cfg.hidden_size)), cache)
    for i, n in enumerate(lens):
        one = afmoe.init_cache(cfg, 1, bucket, dtype=jnp.float32)
        _, one = afmoe.prefill(params, cfg, rows[i:i + 1], mask[i:i + 1], one,
                               last_only=True)
        for name in afmoe.fixed_state(cfg):
            close(cache[name][:, i], one[name][:, 0], 1e-6)
            assert cache[name].shape[2] == W
        want = REF.decoder_logits(
            params, jnp.concatenate([rows[i, :n], nxt]), jnp.arange(n + 1), TOY)
        close(logits[i], want[n - 1])
        close(step[i], want[n])


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """One expert layer's MLP half with all 32 experts held, and the eight
    shares of 4: the routed parts add up; the shared expert, which every
    chip computes alike, is counted once. The program against its own uncut
    layer, and each share against the reference given the same share."""
    whole_hf = hf_of(layer_types=["full_attention"], num_hidden_layers=1,
                     num_dense_layers=0, num_experts=32, experts_offset=0)
    cfg, params = params_of(whole_hf)
    layer = params["layers"][0]
    t = 29
    y = embeds(t, cfg.hidden_size) * 8.0
    counted = jnp.ones((t,), bool)

    def call(c, lay, x):
        return experts_mod.sparse_experts(
            afmoe._routing(c), x, counted, jnp.float32, router=lay["router"],
            bias=lay["expert_bias"], experts=lay["experts"],
            shared=lay["shared"], dense_up_to=afmoe.DENSE_EXPERTS_UP_TO)

    whole, stats = call(cfg, layer, y)
    assert int(stats[2]) == t * 4  # every assignment falls on a held expert
    with jax.default_matmul_precision("highest"):
        sh = layer["shared"]
        shared = (jax.nn.silu(y @ sh["gate"]) * (y @ sh["up"])) @ sh["down"]
    total, held_sum = shared, 0
    for share in range(8):
        lo = 4 * share
        part_cfg = dataclasses.replace(cfg, experts_held=4, experts_offset=lo)
        part = {**layer, "experts": {k: v[lo:lo + 4]
                                     for k, v in layer["experts"].items()}}
        out, st = call(part_cfg, part, y)
        total = total + (out - shared)
        held_sum += int(st[2])
        # the reference, given the same share (its block norms the input and
        # the output and adds the input: ones and eps 0 make the input's norm
        # a division the test does first, and the output's is compared
        # after the same division)
        normed = y / jnp.sqrt((y * y).mean(-1, keepdims=True))
        ones = jnp.ones_like(layer["pre_mlp_norm"])
        ref_out = REF._experts(
            {**part, "pre_mlp_norm": ones, "post_mlp_norm": ones}, y,
            top_k=4, held=4, offset=lo, scale=2.448, route_norm=True,
            eps=0.0, lower=None) - y
        out_n, _ = call(part_cfg, part, normed)
        close(out_n / jnp.sqrt((out_n * out_n).mean(-1, keepdims=True)),
              ref_out, 1e-4)
    assert held_sum == t * 4
    close(total, whole)


def test_the_sliced_vocabulary_is_a_smaller_vocabulary():
    cfg, params = params_of(TOY)
    ids = jnp.asarray([3, 100, 127, 64, 9])  # drawn from the slice
    x = afmoe.embed_tokens(params, ids)
    full = afmoe.forward(params, cfg, x[None])[0]
    cut_cfg = dataclasses.replace(cfg, vocab_size=128)
    cut = {**params, "embed_tokens": params["embed_tokens"][:128],
           "lm_head": params["lm_head"][:, :128]}
    got = afmoe.forward(cut, cut_cfg, afmoe.embed_tokens(cut, ids)[None])[0]
    assert got.shape == (5, 128)
    close(got, full[:, :128], 1e-6)


# -- through ContinuousBatcher ---------------------------------------------------

def _model(seed: int = 0, attn_impl: str = "dense"):
    cfg = from_hf_config(TOY, attn_impl=attn_impl)
    params = eventchat.init_eventchat_params(cfg, jax.random.PRNGKey(seed),
                                             jnp.float32)
    params["llama"] = params_of(TOY, seed)[1]
    return cfg, params


def _request(rng, n_text: int):
    ids = ([1] + [int(i) for i in rng.integers(3, 200, n_text)]
           + [EVENT_TOKEN_INDEX] + [int(i) for i in rng.integers(3, 200, 5)])
    return ids, rng.standard_normal((5, 3, 28, 28)).astype(np.float32)


def _serve(srv, submissions, steps_between: int):
    """Submit in groups, ``steps_between`` scheduler steps apart, one decode
    step a segment; the logits each request's row held after every step in
    which it was live."""
    seen, rids = {}, []
    groups = list(submissions)

    def step():
        srv.step()
        srv._drain()
        logits = np.asarray(srv.logits)
        for row, req in enumerate(srv.rows):
            if req is not None and not srv.frozen[row]:
                seen.setdefault(req.rid, []).append(logits[row].copy())

    while groups or srv.queue or any(r is not None for r in srv.rows):
        if groups:
            for ids, px, budget in groups.pop(0):
                rids.append(srv.submit(ids, px, budget))
            for _ in range(steps_between):
                step()
        else:
            step()
    return rids, seen, dict(srv.finished)


def test_staggered_admissions_into_recycled_slots():
    """Two rows, five requests of unequal prompts (every one past the ring
    of 16: a prompt is 16 + text positions) and budgets long enough to wrap
    the ring again: a slot that a finished request leaves is handed to the
    next one, whose ring is scattered whole. Every request's logits, step by
    step, are the ones it gets alone in a one-row server; a stale ring slot
    fails it."""
    cfg, params = _model()
    rng = np.random.default_rng(0)
    reqs = [_request(rng, n) + (b,) for n, b in
            ((7, 5), (12, 19), (9, 9), (15, 21), (11, 6))]
    srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=1,
                            eos_token_id=None, prefix_cache=False,
                            pipeline=False)
    rids, seen, answers = _serve(srv, [reqs[:3], reqs[3:]], steps_between=2)
    for i, req in enumerate(reqs):
        one = ContinuousBatcher(params, cfg, max_batch=1, max_len=256, chunk=1,
                                eos_token_id=None, prefix_cache=False,
                                pipeline=False)
        (rid,), alone, alone_answers = _serve(one, [[req]], steps_between=1)
        assert answers[rids[i]] == alone_answers[rid]
        assert len(seen[rids[i]]) == len(alone[rid]) > 0
        close(np.stack(seen[rids[i]]), np.stack(alone[rid]), 1e-4)


def test_the_counters_leave_with_the_segment():
    """The expert counters on every span that carries them; and, the prefill
    through the blocked flash kernel, its census of blocks on the prefill's
    span (one block of 128 a layer a row: none of them interior)."""
    from eventgpt_tpu.obs import trace as obs_trace

    cfg, params = _model(attn_impl="flash")
    rng = np.random.default_rng(1)
    obs_trace.configure(4096)
    try:
        srv = ContinuousBatcher(params, cfg, max_batch=2, max_len=256, chunk=3,
                                eos_token_id=None, prefix_cache=False)
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
    n_e = cfg.llama.num_layers - cfg.llama.num_dense_layers
    for args in by_name["dispatch"]:
        steps = len(args["routed_tokens"])
        assert 1 <= steps <= 3
        for name in ("experts_touched", "expert_fullest", "held_assignments",
                     "experts_over_capacity", "expert_tiles"):
            assert len(args[name]) == steps
            assert all(len(step) == n_e for step in args[name])
        for held, tokens in zip(args["held_assignments"],
                                args["routed_tokens"]):
            assert all(h <= 4 * tokens for h in held)
        # grouped at every size: a layer that held an assignment visited a
        # row tile of it
        for held, tiles in zip(args["held_assignments"], args["expert_tiles"]):
            assert all(t >= 1 for t, h in zip(tiles, held) if h > 0)
        # every live row's prompt is past the ring of 16
        assert args["past_window"] == args["live"] >= 1
    prompts = [e["args"] for e in ring if e.get("ph") == "X"
               and e.get("name") == "prefill"]
    assert all(a["positions"] == 128 for a in prompts)
    for a in prompts:
        rows = 1 << (a["n"] - 1).bit_length()
        assert a["flash_blocks"] == cfg.llama.num_layers * rows
        assert a["flash_blocks_interior"] == 0
    prefill, = [d for d in obs_trace.SPANS
                if (d.cat, d.name) == ("admit", "prefill")]
    assert "flash_blocks and flash_blocks_interior" in prefill.brackets


REFUSED = {
    "--kv_cache int8": dict(kv_quant=True),
    "--kv_layout paged": dict(kv_layout="paged"),
    "--speculative": dict(speculative=4),
    "--spec_buckets": dict(spec_buckets="0,2,4"),
    "--prefill_chunk": dict(prefill_chunk=64),
    "--prefill_budget": dict(prefill_budget=8),
    "--prefix_cache_mb": dict(prefix_cache=True),
    "--preempt": dict(preempt=True),
    "--role": dict(role="decode", kv_layout="dense"),
    "--mesh_model": dict(mesh=object()),
    "--draft_head": dict(draft_head={"w": 0}),
}


@pytest.mark.parametrize("flag", sorted(REFUSED))
def test_what_cannot_serve_a_ring_refuses_by_its_flag(flag):
    cfg, params = _model()
    asked = {"prefix_cache": False, **REFUSED[flag]}
    with pytest.raises(ValueError) as e:
        ContinuousBatcher(params, cfg, max_batch=2, max_len=256, **asked)
    assert flag in str(e.value) and "ring" in str(e.value)
    assert afmoe.REFUSES[flag] in str(e.value)


def test_quantization_fusing_and_beams_refuse_too():
    from eventgpt_tpu.models.synthetic import served_shapes

    cfg, params = _model()
    for quant, fuse, flag in (("int8", False, "--quant"),
                              ("none", True, "--fuse_params")):
        with pytest.raises(ValueError, match=flag):
            served_shapes(cfg, jnp.float32, quant, fuse)
    with pytest.raises(ValueError, match="ring"):
        eventchat.generate(params, cfg, [[1, EVENT_TOKEN_INDEX, 5]],
                           np.zeros((1, 5, 3, 28, 28), np.float32),
                           max_new_tokens=2, num_beams=2)
    with pytest.raises(ValueError, match="no ring"):
        afmoe.init_cache(cfg.llama, 1, 64, quant=True)


def test_generate_serves_it_too():
    """The one-shot path picks the decoder's module by the configuration:
    its greedy answer is the continuous batcher's."""
    cfg, params = _model()
    rng = np.random.default_rng(3)
    ids, px = _request(rng, 8)
    (once,) = eventchat.generate(params, cfg, [ids], px[None],
                                 max_new_tokens=20, eos_token_id=None)
    srv = ContinuousBatcher(params, cfg, max_batch=1, max_len=256, chunk=4,
                            eos_token_id=None, prefix_cache=False)
    rid = srv.submit(ids, px, 20)
    assert srv.run_until_drained()[rid] == once


def test_a_wave_is_cut_at_the_decoders_positions(monkeypatch):
    cfg, params = _model()
    rng = np.random.default_rng(4)
    assert afmoe.WAVE_TOKENS < 2 * 12288  # a served prompt is a wave
    monkeypatch.setattr(afmoe, "WAVE_TOKENS", 128)  # one prompt of a bucket
    srv = ContinuousBatcher(params, cfg, max_batch=4, max_len=512, chunk=2,
                            eos_token_id=None, prefix_cache=False)
    sizes = []
    prefill_wave = srv._prefill_wave
    monkeypatch.setattr(srv, "_prefill_wave",
                        lambda wave: (sizes.append(len(wave)),
                                      prefill_wave(wave))[1])
    rids = [srv.submit(*_request(rng, 6), 3) for _ in range(3)]
    out = srv.run_until_drained()
    assert sizes == [] and all(len(out[r]) == 3 for r in rids)


def test_the_memory_estimate_counts_ring_and_plane():
    cfg, params = _model()
    srv = ContinuousBatcher(params, cfg, max_batch=3, max_len=256,
                            eos_token_id=None, prefix_cache=False)
    est = srv.memory_estimate()["components"]
    own = srv.memory_summary()["owner"]
    assert est["kv_cache"] == own["kv_cache"]
    lc = cfg.llama
    slot = 2 * lc.num_kv_heads * lc.resolved_head_dim() * 4
    ring = lc.count("sliding_attention") * lc.sliding_window * slot
    plane = lc.count("full_attention") * 256 * slot
    assert est["kv_cache"] == 3 * (ring + plane + 4) + 4 * 24
