"""Training-path tests: tokenization masking, fixed-layout collation,
LoRA semantics, and full stage-1/stage-2 steps on the tiny model.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eventgpt_tpu.config import EventChatConfig, MeshConfig
from eventgpt_tpu.constants import EVENT_TOKEN_INDEX, IGNORE_INDEX
from eventgpt_tpu.data.tokenizer import load_tokenizer
from eventgpt_tpu.models import eventchat
from eventgpt_tpu.train import data as data_mod
from eventgpt_tpu.train import steps as steps_mod
from eventgpt_tpu.train.lora import LoraConfig, init_lora_params, merge_lora
from eventgpt_tpu.train.optim import linear_warmup_cosine, make_optimizer


@pytest.fixture(scope="module")
def tiny():
    cfg = EventChatConfig.tiny()
    params = eventchat.init_eventchat_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def tokenizer():
    return load_tokenizer("byte")


CONV = [
    {"from": "human", "value": "<event>\nWhat is happening?"},
    {"from": "gpt", "value": "A car turns left."},
    {"from": "human", "value": "Anything else?"},
    {"from": "gpt", "value": "No."},
]


def test_preprocess_v1_masks_human_turns(tokenizer):
    cfg = EventChatConfig.tiny()
    out = data_mod.preprocess_v1(CONV, tokenizer, cfg)
    ids = np.asarray(out["input_ids"])
    labels = np.asarray(out["labels"])
    assert len(ids) == len(labels)
    assert (ids == EVENT_TOKEN_INDEX).sum() == 1
    # Supervised positions decode exactly to the two gpt replies (+ sep2).
    sup = [int(t) for t in labels if t != IGNORE_INDEX]
    text = tokenizer.decode(sup)
    assert "A car turns left." in text and "No." in text
    assert "What is happening?" not in text
    # Every supervised label equals its input id (teacher forcing).
    m = labels != IGNORE_INDEX
    np.testing.assert_array_equal(ids[m], labels[m])


def test_preprocess_plain(tokenizer):
    cfg = EventChatConfig.tiny()
    out = data_mod.preprocess_plain(CONV[:2], tokenizer, cfg)
    ids = np.asarray(out["input_ids"])
    labels = np.asarray(out["labels"])
    assert (ids == EVENT_TOKEN_INDEX).sum() == 1
    sup = [int(t) for t in labels if t != IGNORE_INDEX]
    assert "A car turns left." in tokenizer.decode(sup)


def _mk_samples(cfg, tokenizer, n=2, with_event=True):
    samples = []
    for i in range(n):
        conv = [
            {"from": "human", "value": ("<event>\n" if with_event else "") + f"Q{i}?"},
            {"from": "gpt", "value": f"Answer {i}."},
        ]
        tok = data_mod.preprocess_v1(conv, tokenizer, cfg)
        pix = (np.random.default_rng(i).normal(
            size=(cfg.num_event_frames, 3, cfg.vision.image_size, cfg.vision.image_size)
        ).astype(np.float32) if with_event else None)
        samples.append(data_mod.Sample(tok["input_ids"], tok["labels"], pix))
    return samples


def test_collate_fixed_layout(tiny, tokenizer):
    cfg, _ = tiny
    samples = _mk_samples(cfg, tokenizer, 2)
    batch = data_mod.collate_fixed_layout(samples, cfg, bucket=8)
    e = cfg.num_event_tokens
    b, t = batch["token_ids"].shape
    assert b == 2 and t % 8 == 0
    for i, s in enumerate(samples):
        # Event block: contiguous, length E, labels IGNORE, ids 0.
        pos = np.where(batch["event_pos"][i])[0]
        assert len(pos) == e and (np.diff(pos) == 1).all()
        assert (batch["labels"][i, pos] == IGNORE_INDEX).all()
        assert (batch["token_ids"][i, pos] == 0).all()
        np.testing.assert_array_equal(
            batch["event_index"][i, pos], np.arange(e)
        )
        # Text round-trips: non-event, non-pad ids equal originals minus sentinel.
        keep = batch["attn_mask"][i] & ~batch["event_pos"][i]
        orig = [t for t in s.input_ids if t != EVENT_TOKEN_INDEX]
        np.testing.assert_array_equal(batch["token_ids"][i, keep], orig)


def test_collate_text_only_row(tiny, tokenizer):
    cfg, _ = tiny
    samples = _mk_samples(cfg, tokenizer, 1, with_event=True) + _mk_samples(
        cfg, tokenizer, 1, with_event=False
    )
    batch = data_mod.collate_fixed_layout(samples, cfg)
    assert batch["event_pos"][1].sum() == 0
    assert (batch["pixel_values"][1] == 0).all()


def test_multimodal_embeds_places_event_tokens(tiny, tokenizer):
    cfg, params = tiny
    samples = _mk_samples(cfg, tokenizer, 2)
    host = data_mod.collate_fixed_layout(samples, cfg, bucket=8)
    batch = steps_mod.batch_to_device(host)
    embeds = steps_mod.multimodal_embeds(params, cfg, batch)
    ev = eventchat.encode_events_batch(params, cfg, batch["pixel_values"])
    i = 0
    pos = np.where(host["event_pos"][i])[0]
    np.testing.assert_allclose(
        np.asarray(embeds[i, pos]), np.asarray(ev[i]), rtol=1e-5, atol=1e-5
    )


def test_lora_zero_init_is_identity(tiny):
    cfg, params = tiny
    lcfg = LoraConfig(r=4)
    lora = init_lora_params(cfg.llama, lcfg, jax.random.PRNGKey(1))
    merged = merge_lora(params["llama"], lora, lcfg)
    for g, n in [("attn", "q"), ("mlp", "down")]:
        np.testing.assert_array_equal(
            np.asarray(merged["layers"][g][n]),
            np.asarray(params["llama"]["layers"][g][n]),
        )


def test_lora_dropout_range_validated():
    # Dropout is implemented (tests/test_lora_dropout.py); only the range
    # is policed here.
    with pytest.raises(ValueError):
        LoraConfig(dropout=1.5)
    LoraConfig(dropout=0.1)


def test_apply_lora_matches_merge_lora(tiny):
    """Apply-form (composite leaves, no delta materialization) and merge-form
    produce the same logits for nonzero A/B."""
    from eventgpt_tpu.models import llama as llama_mod
    from eventgpt_tpu.train.lora import apply_lora

    cfg, params = tiny
    lcfg = LoraConfig(r=4)
    lora = init_lora_params(cfg.llama, lcfg, jax.random.PRNGKey(1))
    # Make B nonzero so the delta actually participates.
    lora = jax.tree_util.tree_map(
        lambda x: x + 0.05 * jnp.ones_like(x), lora
    )
    embeds = llama_mod.embed_tokens(params["llama"], jnp.arange(12)[None])
    out_merge = llama_mod.forward(
        merge_lora(params["llama"], lora, lcfg), cfg.llama, embeds
    )
    out_apply = llama_mod.forward(
        apply_lora(params["llama"], lora, lcfg), cfg.llama, embeds
    )
    np.testing.assert_allclose(
        np.asarray(out_apply), np.asarray(out_merge), rtol=2e-4, atol=2e-4
    )


def _train_some_steps(cfg, params, tokenizer, stage, n_steps=4):
    samples = _mk_samples(cfg, tokenizer, 2)
    host = data_mod.collate_fixed_layout(samples, cfg, bucket=8)
    batch = steps_mod.batch_to_device(host)

    opt = make_optimizer(linear_warmup_cosine(1e-2, 100, 0))
    if stage == 1:
        trainable, frozen = steps_mod.split_stage1(params)
        combine = steps_mod.stage1_combine
    else:
        lcfg = LoraConfig(r=4)
        trainable, frozen = steps_mod.split_stage2(
            params, cfg, lcfg, jax.random.PRNGKey(2)
        )
        combine = steps_mod.make_stage2_combine(lcfg)
    state = steps_mod.init_train_state(trainable, frozen, opt)
    step_fn = steps_mod.make_train_step(cfg, opt, combine, donate=False)
    losses = []
    for _ in range(n_steps):
        state, m = step_fn(state, batch)
        losses.append(float(m["loss"]))
    return state, losses, frozen


def test_stage1_step_trains_projector_only(tiny, tokenizer):
    cfg, params = tiny
    state, losses, frozen = _train_some_steps(cfg, params, tokenizer, stage=1)
    assert losses[-1] < losses[0], losses
    # Frozen trees bit-identical.
    for a, b in zip(
        jax.tree_util.tree_leaves(frozen), jax.tree_util.tree_leaves(state.frozen)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Projector actually moved.
    moved = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(
            jax.tree_util.tree_leaves(params["projector"]),
            jax.tree_util.tree_leaves(state.trainable["projector"]),
        )
    )
    assert moved


def test_stage2_lora_step(tiny, tokenizer):
    cfg, params = tiny
    state, losses, _ = _train_some_steps(cfg, params, tokenizer, stage=2)
    assert losses[-1] < losses[0], losses
    # LoRA B started at zero and moved.
    b_leaf = state.trainable["lora"]["attn"]["q"]["b"]
    assert float(jnp.abs(b_leaf).sum()) > 0


def test_remat_policy_sweep_loss_equality(tiny, tokenizer):
    """ISSUE 13 satellite (VERDICT r5 / ROADMAP item 4 enabler): the
    stage-2 step under every jax.checkpoint policy computes the SAME
    loss and the same update as full remat — the policy only moves
    backward-pass memory/recompute, never values."""
    import dataclasses

    cfg, params = tiny
    samples = _mk_samples(cfg, tokenizer, 2)
    host = data_mod.collate_fixed_layout(samples, cfg, bucket=8)
    batch = steps_mod.batch_to_device(host)
    lcfg = LoraConfig(r=4)

    def one_step(policy):
        pcfg = dataclasses.replace(
            cfg, llama=dataclasses.replace(cfg.llama, remat_policy=policy))
        trainable, frozen = steps_mod.split_stage2(
            params, pcfg, lcfg, jax.random.PRNGKey(2))
        opt = make_optimizer(linear_warmup_cosine(1e-2, 100, 0))
        state = steps_mod.init_train_state(trainable, frozen, opt)
        step_fn = steps_mod.make_train_step(pcfg, opt,
                                            steps_mod.make_stage2_combine(lcfg),
                                            donate=False)
        state, m = step_fn(state, batch)
        return float(m["loss"]), state.trainable

    base_loss, base_tr = one_step("full")
    for policy in ("nothing_saveable", "dots_saveable",
                   "dots_with_no_batch_dims_saveable"):
        loss, tr = one_step(policy)
        np.testing.assert_allclose(loss, base_loss, rtol=1e-6,
                                   err_msg=policy)
        for a, b in zip(jax.tree_util.tree_leaves(base_tr),
                        jax.tree_util.tree_leaves(tr)):
            # A rematerialised backward re-associates float32 sums, and
            # one AdamW step divides each gradient element by its own
            # magnitude: where a gradient is near zero the update moves.
            # Seen: 1 of 4,096 elements of one leaf off by 1.9e-6
            # absolute (7.5e-5 relative) under dots_saveable, at a
            # learning rate of 1e-2.
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=5e-6,
                                       err_msg=policy)


def test_remat_policy_validated():
    import dataclasses

    from eventgpt_tpu.config import LlamaConfig

    with pytest.raises(ValueError, match="remat_policy"):
        dataclasses.replace(LlamaConfig(), remat_policy="typo_saveable")


def test_lm_loss_ignores_masked_positions():
    logits = jnp.zeros((1, 4, 8))
    labels = jnp.asarray([[IGNORE_INDEX, 3, IGNORE_INDEX, 5]])
    loss, n = steps_mod.lm_loss(logits, labels)
    assert int(n) == 2
    np.testing.assert_allclose(float(loss), np.log(8), rtol=1e-6)


def test_end_to_end_dataset_and_iterator(tmp_path, tiny, tokenizer):
    cfg, params = tiny
    # Build a toy dataset file pointing at the reference sample.
    sample = "/root/reference/samples/sample1.npy"
    if not os.path.exists(sample):
        pytest.skip("reference sample not available")
    entries = [
        {"id": i,
         "event": "sample1.npy",
         "conversations": [
             {"from": "human", "value": "<event>\nDescribe."},
             {"from": "gpt", "value": f"Scene {i}."},
         ]}
        for i in range(4)
    ]
    data_path = tmp_path / "qa.json"
    data_path.write_text(json.dumps(entries))
    ds = data_mod.EventChatDataset(
        str(data_path), tokenizer, cfg,
        event_folder="/root/reference/samples",
    )
    assert len(ds) == 4
    assert ds.modality_lengths()[0] > 0
    batches = list(data_mod.batch_iterator(ds, 2, cfg, shuffle=True))
    assert len(batches) == 2
    assert batches[0]["pixel_values"].shape[1] == cfg.num_event_frames
