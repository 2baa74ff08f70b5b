"""The hybrid decoder (``models/nemotron_h.py``) against its plain reference
(``benchmark/references/nemotron_h.py``) at toy widths on the CPU: logits,
not tokens. float32 throughout, so the tolerances are those of two orders of
float32 summation: 2e-4 absolute on logits of magnitude ~5 (the chunked scan
sums a chunk's products where the reference walks positions; the grouped
product sums an expert's rows in another order), never a rounding of
bfloat16 size (4e-3 relative)."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import loader
from eventgpt_tpu.config import HybridConfig, from_hf_config
from eventgpt_tpu.constants import EVENT_TOKEN_INDEX
from eventgpt_tpu.models import eventchat, nemotron_h as nh
from eventgpt_tpu.serve import ContinuousBatcher

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS") != "cpu", reason="a CPU test")

TOL = 2e-4
TOY = loader.read_json(os.path.join(loader.HERE, "configs",
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
        for name in ("conv_b", "e_score_correction_bias"):
            if name in layer:
                key, sub = jax.random.split(key)
                layer[name] = 0.1 * jax.random.normal(sub, layer[name].shape)
        for name in ("norm", "gate_norm", "D"):
            if name in layer:
                key, sub = jax.random.split(key)
                layer[name] = 1.0 + 0.2 * jax.random.normal(
                    sub, layer[name].shape)
    params["embed_tokens"] = params["embed_tokens"] * 50.0  # unit tables
    return cfg, params


def embeds(t: int, d: int, seed: int = 2):
    return jax.random.normal(jax.random.PRNGKey(seed), (t, d), jnp.float32)


def close(got, want, tol=TOL):
    err = float(jnp.max(jnp.abs(jnp.asarray(got) - jnp.asarray(want))))
    assert err <= tol, err


def test_from_hf_config_reads_the_published_keys():
    cfg = from_hf_config(TOY, attn_impl="dense").llama
    assert isinstance(cfg, HybridConfig)
    assert cfg.pattern == "MEMEMEM*EME" and cfg.num_layers == 11
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.experts_offset) == (32, 8, 8)
    assert cfg.rms_norm_eps == TOY["layer_norm_epsilon"]
    assert cfg.count("M") == 5 and cfg.count("E") == 5 and cfg.count("*") == 1
    short = from_hf_config(hf_of(num_hidden_layers=3), attn_impl="dense").llama
    assert short.pattern == "MEM"
    with pytest.raises(ValueError, match="num_hidden_layers"):
        from_hf_config(hf_of(num_hidden_layers=99), attn_impl="dense")
    # a dense file still builds the dense decoder
    dense = loader.read_json(os.path.join(loader.HERE, "configs",
                                          "rehearsal-tiny.json"))
    assert not isinstance(from_hf_config(dense, attn_impl="dense").llama,
                          HybridConfig)
    # and the configuration survives its own serialisation
    from eventgpt_tpu.config import event_chat_config_from_dict, to_dict

    whole = from_hf_config(TOY, attn_impl="dense")
    assert event_chat_config_from_dict(to_dict(whole)) == whole


@pytest.mark.parametrize("kind, t", [("M", 37), ("*", 37), ("E", 37),
                                     ("E", nh.DENSE_EXPERTS_UP_TO + 22)])
def test_each_mixer_alone(kind, t):
    """The expert layer in both forms of its product: a few tokens against
    every held expert, and more tokens sorted by expert."""
    hf = hf_of(hybrid_override_pattern=kind, num_hidden_layers=1)
    cfg, params = params_of(hf)
    x = embeds(t, cfg.hidden_size)
    want = REF.decoder_logits(params, x, jnp.arange(t), hf)
    close(nh.forward(params, cfg, x[None])[0], want)


@pytest.mark.parametrize("t", [128, 256, 100, 300])
def test_chunked_scan_against_the_sequential_one(t):
    """chunk_size 128 as published: whole chunks, and lengths that end
    inside one."""
    hf = hf_of(hybrid_override_pattern="MM", num_hidden_layers=2,
               chunk_size=128)
    cfg, params = params_of(hf)
    x = embeds(t, cfg.hidden_size)
    want = REF.decoder_logits(params, x, jnp.arange(t), hf)
    close(nh.forward(params, cfg, x[None])[0], want)


def test_prefill_then_decode_through_the_cache():
    cfg, params = params_of(TOY)
    p, steps = 37, 8
    x = embeds(p + steps, cfg.hidden_size)
    want = REF.decoder_logits(params, x, jnp.arange(p + steps), TOY)
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
    # what the expert layers counted: one token, at most top_k assignments
    stats = np.asarray(cache["moe_stats"])
    assert stats.shape == (cfg.count("E"), len(nh.STATS))
    assert (stats[:, 3] == 1).all() and (stats[:, 2] <= 6).all()
    assert (stats[:, 0] == stats[:, 2]).all()  # one token: an expert once


def test_a_row_that_is_not_live_keeps_its_state():
    cfg, params = params_of(TOY)
    x = embeds(20, cfg.hidden_size)
    cache = nh.init_cache(cfg, 2, 32, jnp.float32)
    both = jnp.stack([x[:16], x[:16]])
    _, cache = nh.prefill(params, cfg, both, jnp.ones((2, 16), bool), cache,
                          last_only=True)
    live = jnp.asarray([True, False])
    step = jnp.stack([x[16:17], x[17:18]])
    _, after = nh.decode_step(params, cfg, step, cache, live=live)
    for plane in ("conv", "h"):
        assert jnp.array_equal(after[plane][:, 1], cache[plane][:, 1])
        assert not jnp.array_equal(after[plane][:, 0], cache[plane][:, 0])
    assert int(after["moe_stats"][0, 3]) == 1  # the live row alone is counted


def test_a_right_padded_wave_against_each_row_alone():
    cfg, params = params_of(TOY)
    lens = [5, 23, 32, 2]
    x = embeds(32, cfg.hidden_size)
    rows = jnp.stack([jnp.where((jnp.arange(32) < n)[:, None],
                                x * (1.0 + 0.1 * i), 0.0)
                      for i, n in enumerate(lens)])
    mask = jnp.arange(32)[None, :] < jnp.asarray(lens)[:, None]
    cache = nh.init_cache(cfg, 4, 40, jnp.float32)  # room for one more token
    logits, cache = nh.prefill(params, cfg, rows, mask, cache, last_only=True)
    nxt = embeds(1, cfg.hidden_size, seed=9)
    step, after = nh.decode_step(params, cfg,
                                 jnp.broadcast_to(nxt, (4, 1, nxt.shape[-1])),
                                 cache)
    for i, n in enumerate(lens):
        one = nh.init_cache(cfg, 1, 32, jnp.float32)
        alone, one = nh.prefill(params, cfg, rows[i:i + 1, :n],
                                jnp.ones((1, n), bool), one, last_only=True)
        close(logits[i], alone[0])
        close(cache["conv"][:, i], one["conv"][:, 0], 1e-5)
        close(cache["h"][:, i], one["h"][:, 0], 1e-5)
        # and against the reference, through one more token
        want = REF.decoder_logits(
            params, jnp.concatenate([rows[i, :n], nxt]), jnp.arange(n + 1), TOY)
        close(logits[i], want[n - 1])
        close(step[i], want[n])


@pytest.mark.parametrize("t", [29, nh.DENSE_EXPERTS_UP_TO + 22])
def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer(t):
    """One ``E`` block with all 32 experts held, and the four shares of 8:
    the routed parts (through the latent's up-projection, which is linear)
    add up; the shared expert, which every chip computes alike, is counted
    once. The program and the reference, each against its own uncut layer
    and against each other."""
    whole_hf = hf_of(hybrid_override_pattern="E", num_hidden_layers=1,
                     n_routed_experts=32, experts_offset=0)
    cfg, params = params_of(whole_hf)
    layer = params["layers"][0]
    y = embeds(t, cfg.hidden_size)
    counted = jnp.ones((t,), bool)
    whole, stats = nh._moe_block(cfg, layer, y, counted, jnp.float32)
    assert int(stats[2]) == t * 6  # every assignment falls on a held expert
    with jax.default_matmul_precision("highest"):
        shared = jnp.square(jax.nn.relu(y @ layer["shared_up"])) \
            @ layer["shared_down"]
    total = shared
    held_sum = 0
    for share in range(4):
        part_cfg = dataclasses.replace(cfg, experts_held=8,
                                       experts_offset=8 * share)
        part = {**layer,
                "experts_up": layer["experts_up"][8 * share:8 * share + 8],
                "experts_down": layer["experts_down"][8 * share:8 * share + 8]}
        out, st = nh._moe_block(part_cfg, part, y, counted, jnp.float32)
        total = total + (out - shared)
        held_sum += int(st[2])
        # the reference, given the same share (its block adds the input and
        # norms it first: undo both)
        part_hf = hf_of(hybrid_override_pattern="E", num_hidden_layers=1,
                        n_routed_experts=8, experts_offset=8 * share)
        ref_out = REF._experts(
            {**part, "norm": jnp.ones_like(layer["norm"])}, y,
            top_k=6, held=8, offset=8 * share, scaling=5.0, norm_topk=True,
            eps=0.0, lower=None) - y
        normed = y / jnp.sqrt((y * y).mean(-1, keepdims=True))
        out_n, _ = nh._moe_block(part_cfg, part, normed, counted, jnp.float32)
        close(out_n, ref_out)
        assert part_hf["published"]["n_routed_experts"] == 32
    assert held_sum == t * 6
    close(total, whole)


def _moe_block_before_it_was_shared(cfg, layer, y, counted, dtype):
    """``nemotron_h._moe_block`` as it stood before ``models/experts.py``
    (PR 32's tree), kept to hold the shared layer to it bit for bit."""
    from jax import lax

    from eventgpt_tpu.ops.quant import matmul as mm, matmul_f32_out as mm_f32

    relu2 = lambda v: jnp.square(jax.nn.relu(v))
    per_expert = lambda key, held: jnp.sum(
        key[:, None] == jnp.arange(held)[None, :], axis=0, dtype=jnp.int32)
    t = y.shape[0]
    k, held = cfg.num_experts_per_tok, cfg.experts_held
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(y @ layer["router"].astype(jnp.float32))
    _, experts = lax.top_k(s + layer["e_score_correction_bias"], k)
    w = jnp.take_along_axis(s, experts, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * cfg.routed_scaling_factor
    y = y.astype(dtype)
    u = mm(y, layer["latent_down"])
    local = experts - cfg.experts_offset
    mine = (local >= 0) & (local < held)
    if t <= nh.DENSE_EXPERTS_UP_TO:
        weight = jnp.zeros((t, held + 1), jnp.float32).at[
            jnp.arange(t)[:, None], jnp.where(mine, local, held)
        ].set(jnp.where(mine, w, 0.0))[:, :held]
        a = jnp.einsum("tl,elf->etf", u, layer["experts_up"])
        o = jnp.einsum("etf,efl->etl", relu2(a), layer["experts_down"])
        routed = jnp.einsum("etl,te->tl", o.astype(jnp.float32), weight)
    else:
        key = jnp.where(mine, local, held).reshape(t * k)
        order = jnp.argsort(key)
        sizes = per_expert(key, held)
        a = lax.ragged_dot(u[order // k], layer["experts_up"], sizes)
        o = lax.ragged_dot(relu2(a), layer["experts_down"], sizes)
        o = jnp.where(mine[..., None],
                      o[jnp.argsort(order)].reshape(t, k, -1), 0)
        routed = jnp.einsum("tkl,tk->tl", o.astype(jnp.float32), w)
    routed = mm_f32(routed.astype(dtype), layer["latent_up"])
    shared = mm_f32(relu2(mm(y, layer["shared_up"])), layer["shared_down"])
    load = per_expert(jnp.where(mine & counted[:, None], local, held)
                      .reshape(t * k), held)
    stats = jnp.stack([jnp.sum(load > 0), jnp.max(load), jnp.sum(load),
                       jnp.sum(counted)]).astype(jnp.int32)
    return routed + shared, stats


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t", [29, nh.DENSE_EXPERTS_UP_TO + 22])
def test_the_shared_expert_layer_is_bit_for_bit_the_hybrids_own(t, dtype):
    """Both forms of the held experts' product, in both compute types,
    jitted as the server runs them: the layer that two decoders now call
    gives the hybrid what its own gave, to the last bit, counters too (its
    four are the first four of the shared layer's five; these calls have no
    capacity to pass: a quarter of the experts held, twice the even share is
    half the assignments, so the grouped product is the one over all)."""
    cfg, params = params_of(hf_of(hybrid_override_pattern="E",
                                  num_hidden_layers=1))
    layer = params["layers"][0]
    y = embeds(t, cfg.hidden_size)
    counted = jnp.arange(t) % 3 != 0
    new = jax.jit(lambda l, y: nh._moe_block(cfg, l, y, counted, dtype))
    old = jax.jit(lambda l, y: _moe_block_before_it_was_shared(
        cfg, l, y, counted, dtype))
    (out, stats), (want, want_stats) = new(layer, y), old(layer, y)
    assert out.dtype == want.dtype and bool(jnp.all(out == want))
    assert bool(jnp.all(stats[:4] == want_stats)) and int(stats[3]) == int(
        counted.sum())
    assert int(stats[4]) == 0


def test_the_sliced_vocabulary_is_a_smaller_vocabulary():
    """A quarter of the rows of the table and of the head's columns: the
    logits over the slice are the whole model's logits at the slice's ids."""
    cfg, params = params_of(TOY)
    ids = jnp.asarray([3, 100, 127, 64, 9])  # drawn from the slice
    x = nh.embed_tokens(params, ids)
    full = nh.forward(params, cfg, x[None])[0]
    cut_cfg = dataclasses.replace(cfg, vocab_size=128)
    cut = {**params, "embed_tokens": params["embed_tokens"][:128],
           "lm_head": params["lm_head"][:, :128]}
    got = nh.forward(cut, cut_cfg, nh.embed_tokens(cut, ids)[None])[0]
    assert got.shape == (5, 128)
    close(got, full[:, :128], 1e-6)


# -- through ContinuousBatcher ---------------------------------------------------

def _model(seed: int = 0):
    cfg = from_hf_config(TOY, attn_impl="dense")
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
    """Two rows, five requests of unequal prompts and budgets: a slot that a
    finished request leaves is handed to the next one, which must start from
    its own state. Every request's logits, step by step, are the ones it
    gets alone in a one-row server; a stale ``h`` or conv tail fails it."""
    cfg, params = _model()
    rng = np.random.default_rng(0)
    reqs = [_request(rng, n) + (b,) for n, b in
            ((7, 3), (12, 9), (9, 6), (15, 7), (11, 5))]
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
    from eventgpt_tpu.obs import trace as obs_trace

    cfg, params = _model()
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
    for args in by_name["dispatch"]:
        steps = len(args["routed_tokens"])
        assert 1 <= steps <= 3
        assert all(1 <= t <= 2 for t in args["routed_tokens"])
        for name in ("experts_touched", "expert_fullest", "held_assignments",
                     "experts_over_capacity", "expert_tiles"):
            assert len(args[name]) == steps
            assert all(len(step) == cfg.llama.count("E") for step in args[name])
        for touched, held, tokens in zip(args["experts_touched"],
                                         args["held_assignments"],
                                         args["routed_tokens"]):
            assert all(0 <= t <= min(8, h) for t, h in zip(touched, held))
            assert all(h <= 6 * tokens for h in held)
        # a decode step is batched: no grouped product, no row tile
        assert all(t == 0 for step in args["expert_tiles"] for t in step)
    keys = ("experts_touched", "expert_fullest", "held_assignments",
            "routed_tokens", "experts_over_capacity", "expert_tiles")
    assert ([[a[k] for k in keys] for a in by_name["dispatch"]]
            == [[a[k] for k in keys] for a in by_name["harvest"]])
    (wave,) = by_name["prefill"]  # both requests met at one boundary
    assert wave["routed_tokens"] == [  # real positions only
        sum(len(r) + cfg.num_event_tokens - 1 for r in ([0] * 14, [0] * 16))]


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
}


@pytest.mark.parametrize("flag", sorted(REFUSED))
def test_what_carries_no_recurrent_state_refuses_by_its_flag(flag):
    cfg, params = _model()
    asked = {"prefix_cache": False, **REFUSED[flag]}
    with pytest.raises(ValueError) as e:
        ContinuousBatcher(params, cfg, max_batch=2, max_len=256, **asked)
    assert flag in str(e.value) and "recurrent state" in str(e.value)


def test_a_mesh_a_draft_head_quantization_and_fusing_refuse_too():
    from eventgpt_tpu.models.synthetic import served_shapes

    cfg, params = _model()
    with pytest.raises(ValueError, match="--mesh_model"):
        ContinuousBatcher(params, cfg, max_batch=2, max_len=256,
                          prefix_cache=False, mesh=object())
    with pytest.raises(ValueError, match="--draft_head"):
        ContinuousBatcher(params, cfg, max_batch=2, max_len=256,
                          prefix_cache=False, draft_head={"w": 0})
    for quant, fuse, flag in (("int8", False, "--quant"),
                              ("none", True, "--fuse_params")):
        with pytest.raises(ValueError, match=flag):
            served_shapes(cfg, jnp.float32, quant, fuse)
    with pytest.raises(ValueError, match="recurrent state"):
        eventchat.generate(params, cfg, [[1, EVENT_TOKEN_INDEX, 5]],
                           np.zeros((1, 5, 3, 28, 28), np.float32),
                           max_new_tokens=2, num_beams=2)


def test_generate_serves_the_hybrid_too():
    """The one-shot path picks the decoder's module by the configuration:
    its greedy answer is the continuous batcher's."""
    cfg, params = _model()
    rng = np.random.default_rng(3)
    ids, px = _request(rng, 8)
    (once,) = eventchat.generate(params, cfg, [ids], px[None],
                                 max_new_tokens=6, eos_token_id=None)
    srv = ContinuousBatcher(params, cfg, max_batch=1, max_len=256, chunk=4,
                            eos_token_id=None, prefix_cache=False)
    rid = srv.submit(ids, px, 6)
    assert srv.run_until_drained()[rid] == once


def test_a_wave_is_cut_at_the_decoders_positions(monkeypatch):
    cfg, params = _model()
    rng = np.random.default_rng(4)
    monkeypatch.setattr(nh, "WAVE_TOKENS", 2 * 128)  # two prompts of one bucket
    srv = ContinuousBatcher(params, cfg, max_batch=4, max_len=512, chunk=2,
                            eos_token_id=None, prefix_cache=False)
    sizes = []
    prefill_wave = srv._prefill_wave
    monkeypatch.setattr(srv, "_prefill_wave",
                        lambda wave: (sizes.append(len(wave)),
                                      prefill_wave(wave))[1])
    rids = [srv.submit(*_request(rng, 6), 3) for _ in range(4)]
    out = srv.run_until_drained()
    assert sizes == [2, 2] and all(len(out[r]) == 3 for r in rids)


def test_the_memory_estimate_counts_both_kinds_of_state():
    cfg, params = _model()
    srv = ContinuousBatcher(params, cfg, max_batch=3, max_len=256,
                            eos_token_id=None, prefix_cache=False)
    est = srv.memory_estimate()["components"]
    own = srv.memory_summary()["owner"]
    assert est["kv_cache"] == own["kv_cache"]
    lc = cfg.llama
    h = lc.mamba_num_heads * lc.mamba_head_dim * lc.ssm_state_size * 4
    conv = (lc.conv_kernel - 1) * lc.conv_channels * 4
    kv = 2 * lc.num_kv_heads * lc.resolved_head_dim() * 4 * 256
    assert est["kv_cache"] == 3 * (5 * (h + conv) + kv + 4) + 5 * 24
