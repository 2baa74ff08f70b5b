"""Inference CLI: event stream + question -> answer, on TPU.

Flag parity with the reference entry point (``inference.py:12-26``); the
load-prep-generate-decode flow mirrors ``inference.py:28-66`` with the TPU
pipeline underneath (jit CLIP encode, pjit-able LLaMA, HBM KV cache).

Usage:
  python -m eventgpt_tpu.cli.infer \\
      --model_path <hf_ckpt_dir|tiny-random|eventgpt-7b-random> \\
      --event_frame samples/sample1.npy --query "What is happening?"

``--model_path tiny-random`` runs the full pipeline with tiny random weights
and the offline byte tokenizer (no checkpoint/network needed) — useful as a
smoke test of the end-to-end path. ``eventgpt-7b-random`` is the same at
EventGPT-7B widths, from a seed (``models/synthetic.py``; use ``--quant
int8`` on one 16 GB chip).
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from eventgpt_tpu import constants
from eventgpt_tpu.config import EventChatConfig, from_hf_config
from eventgpt_tpu.data.conversation import prepare_event_prompt
from eventgpt_tpu.data.tokenizer import load_tokenizer, tokenize_with_event
from eventgpt_tpu.models import convert, eventchat
from eventgpt_tpu.models.llama import resize_token_embeddings
from eventgpt_tpu.ops.image import process_event_file


def _str2bool(v: str) -> bool:
    if v.lower() in ("true", "1", "yes"):
        return True
    if v.lower() in ("false", "0", "no"):
        return False
    raise argparse.ArgumentTypeError(f"expected bool, got {v!r}")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="EventGPT-TPU inference")
    p.add_argument("--model_path", type=str, required=True)
    p.add_argument("--model_base", type=str, default=None)
    p.add_argument("--tokenizer_path", type=str, default=None,
                   help="tokenizer assets dir (default: model_path; 'byte' = "
                        "offline byte tokenizer)")
    p.add_argument("--query", type=str, required=True)
    p.add_argument("--conv_mode", type=str, default="eventgpt_v1")
    p.add_argument("--sep", type=str, default=",")
    p.add_argument("--context_len", type=int, default=2048)
    p.add_argument("--temperature", type=float, default=0.6)
    p.add_argument("--top_p", type=float, default=1.0)
    p.add_argument("--num_beams", type=int, default=1)
    p.add_argument("--max_new_tokens", type=int, default=512)
    p.add_argument("--spatial_temporal_encoder", type=_str2bool, default=True,
                   help="pool frame features spatio-temporally (reference default)")
    p.add_argument("--event_frame", type=str, required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--dtype", type=str, default="bfloat16",
                   choices=["bfloat16", "float32"])
    p.add_argument("--attn_impl", type=str, default=None,
                   choices=["dense", "flash"],
                   help="prefill attention kernel (default: flash on TPU)")
    p.add_argument("--quant", type=str, default="none",
                   choices=["none", "int8"],
                   help="weight-only quantization of the LM matmuls")
    p.add_argument("--kv_cache", type=str, default="bf16", choices=["bf16", "int8"],
                   help="KV cache storage (int8 halves cache memory/bandwidth)")
    p.add_argument("--fuse_params", action="store_true",
                   help="fuse q|k|v and gate|up weights (5 matmuls/layer "
                        "instead of 7; helps wide batches)")
    # Serving mesh (BASELINE north star: pjit-sharded FSDP/TP serving).
    # data*fsdp*model must equal the devices used; 1/1/1 = single chip.
    p.add_argument("--mesh_data", type=int, default=1,
                   help="data-parallel axis of the serving mesh")
    p.add_argument("--mesh_fsdp", type=int, default=1,
                   help="ZeRO/FSDP weight-sharding axis of the serving mesh")
    p.add_argument("--mesh_model", type=int, default=1,
                   help="tensor-parallel axis of the serving mesh")
    p.add_argument("--speculative", type=int, default=0,
                   help="speculative decode window (suffix-lookup draft + "
                        "K-token verify; exact greedy chain at temperature "
                        "0, exact sampling distribution above; num_beams "
                        "must be 1; 0 = off)")
    p.add_argument("--draft_head", default=None,
                   help="path to a trained Medusa head stack (.npz from "
                        "train.medusa.save_medusa); replaces the lookup "
                        "draft (requires --speculative > 0)")
    p.add_argument("--timing", action="store_true", help="print stage timings to stderr")
    # Q-Former serving (the use_event_qformer surface): enable the gate and
    # load the trained component artifacts written by the trainer
    # (query_embedder_*.npz / attention_layers_*.npz, reference prefix
    # conventions per model/EventChatModel.py:141-163).
    p.add_argument("--use_event_qformer", action="store_true")
    p.add_argument("--pretrain_query_embedder", type=str, default=None)
    p.add_argument("--pretrain_attention_layers", type=str, default=None)
    return p


def model_config_and_tokenizer(model_path: str, attn_impl=None,
                               tokenizer_path=None):
    """(config, tokenizer) of a ``--model_path`` without its weights: the
    two checkpoint-free spellings (``tiny-random``, ``eventgpt-7b-random``
    — offline byte tokenizer) or an HF checkpoint directory.
    ``attn_impl=None`` on a checkpoint resolves per platform, which
    initialises the backend (``config.default_attn_impl``)."""
    from eventgpt_tpu.models.synthetic import SYNTHETIC_7B

    if model_path == "tiny-random":
        return EventChatConfig.tiny(), load_tokenizer("byte")
    if model_path == SYNTHETIC_7B:
        return EventChatConfig.eventgpt_7b(), load_tokenizer("byte")
    with open(os.path.join(model_path, "config.json")) as f:
        hf_cfg = json.load(f)
    cfg = from_hf_config(hf_cfg, attn_impl=attn_impl)
    return cfg, load_tokenizer(tokenizer_path or model_path)


def load_model(model_path: str, dtype: str, attn_impl=None, tokenizer_path=None,
               quant: str = "none", fuse: bool = False):
    """Returns (config, host-or-device params, tokenizer).

    HF-checkpoint params stay host-resident (numpy) so downstream transforms
    (embedding resize, int8 quantization) run before anything hits HBM —
    quantizing a 7B tree on-device would need bf16 + int8 + f32 temps
    simultaneously. ``place_params`` does the final device put.

    ``quant`` / ``fuse`` matter to ``eventgpt-7b-random`` only: its seeded
    host tree is built directly at the fused / quantized shapes
    (``models/synthetic.py``), and ``prepare_model`` leaves it as it is.
    """
    import jax.numpy as jnp

    from eventgpt_tpu.models import synthetic

    jdt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    cfg, tokenizer = model_config_and_tokenizer(
        model_path, attn_impl, tokenizer_path)
    if model_path == "tiny-random":
        params = eventchat.init_eventchat_params(cfg, jax.random.PRNGKey(0), jdt)
    elif model_path == synthetic.SYNTHETIC_7B:
        params = synthetic.random_eventchat_params(cfg, jdt, quant, fuse)
    else:
        sd = convert.load_state_dict(model_path)
        params = convert.eventchat_params_from_hf(sd, cfg)
    return cfg, params, tokenizer


def place_params(tree, jdt):
    """Host tree -> device, compute floats in ``jdt``; quantized leaves keep
    int8 payloads and f32 scales."""
    import jax.numpy as jnp

    from eventgpt_tpu.ops import quant as quant_mod

    if quant_mod.is_quantized(tree):
        return {"q": jnp.asarray(tree["q"]), "s": jnp.asarray(tree["s"], jnp.float32)}
    if isinstance(tree, dict):
        return {k: place_params(v, jdt) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(place_params(v, jdt) for v in tree)
    return jnp.asarray(tree, jdt)


def _fuse_and_quantize(llama, args):
    """The dense decoder's subtree as ``--fuse_params`` / ``--quant`` ask.
    The full-width synthetic tree (models/synthetic.py) arrives fused /
    quantized already; the tree itself says so."""
    fused = "qkv" in llama["layers"]["attn"]
    quantized = isinstance(llama["lm_head"], dict)
    if getattr(args, "fuse_params", False) and not fused:
        from eventgpt_tpu.models.llama import fuse_llama_params

        # Fuse BEFORE quantization so scales are computed on (and stream
        # with) the fused tensors (models/llama.py:fuse_llama_params).
        llama = fuse_llama_params(llama)
    if args.quant == "int8" and not quantized:
        from eventgpt_tpu.ops.quant import quantize_llama_params

        llama = quantize_llama_params(
            jax.tree_util.tree_map(np.asarray, llama), host=True)
    return llama


def prepare_model(cfg, params, tokenizer, args, mesh=None):
    """Shared post-load preparation for the infer/eval CLIs: optional
    spatio-temporal / Q-Former config gating, special-token registration
    (parity with inference.py:33-39), embedding resize, host-side
    quantization, device placement. Order is load-bearing: the resize must
    precede quantization (quantized leaves are {"q","s"} dicts that
    resize_token_embeddings cannot grow), and quantization runs on host so
    HBM never holds the bf16 and quantized trees together.

    Returns (cfg, params) with params device-placed.
    """
    if getattr(args, "spatial_temporal_encoder", None) is not None and (
        args.spatial_temporal_encoder != cfg.use_spatio_temporal_pool
    ):
        import dataclasses

        cfg = dataclasses.replace(cfg, use_spatio_temporal_pool=args.spatial_temporal_encoder)
    if args.use_event_qformer or cfg.use_event_qformer:
        import dataclasses

        from eventgpt_tpu.config import QFormerConfig
        from eventgpt_tpu.models.qformer import (
            init_qformer_params, load_qformer_components,
        )

        if not cfg.use_event_qformer:
            qcfg = QFormerConfig(hidden_size=cfg.llama.hidden_size)
            if args.pretrain_query_embedder or args.pretrain_attention_layers:
                from eventgpt_tpu.models.qformer import qformer_config_from_artifacts

                qcfg = qformer_config_from_artifacts(
                    args.pretrain_query_embedder, args.pretrain_attention_layers
                )
            cfg = dataclasses.replace(cfg, use_event_qformer=True, qformer=qcfg)
        # Component artifacts exported next to the checkpoint
        # (models/convert.py:write_hf_checkpoint) load automatically;
        # explicit flags override.
        qe_path = args.pretrain_query_embedder
        al_path = args.pretrain_attention_layers
        if qe_path is None and os.path.isdir(args.model_path):
            cand = os.path.join(args.model_path, "query_embedder.npz")
            qe_path = cand if os.path.exists(cand) else None
        if al_path is None and os.path.isdir(args.model_path):
            cand = os.path.join(args.model_path, "attention_layers.npz")
            al_path = cand if os.path.exists(cand) else None
        if "qformer" not in params:
            if (not (qe_path or al_path)) and not args.use_event_qformer:
                # The gate came from the checkpoint's config but no weights
                # exist anywhere: serving a freshly random-initialized
                # Q-Former would silently answer garbage. (The explicit
                # --use_event_qformer flag keeps fresh-init for smoke runs.)
                raise ValueError(
                    f"{args.model_path} gates use_event_qformer but no "
                    f"component artifacts were found in the checkpoint dir "
                    f"or given via --pretrain_query_embedder/"
                    f"--pretrain_attention_layers"
                )
            params["qformer"] = init_qformer_params(
                cfg.qformer, jax.random.PRNGKey(args.seed + 1)
            )
        if qe_path or al_path:
            params["qformer"] = load_qformer_components(
                params["qformer"],
                query_embedder_path=qe_path,
                attention_layers_path=al_path,
            )

    if cfg.mm_use_im_patch_token:
        tokenizer.add_tokens([constants.DEFAULT_EVENT_PATCH_TOKEN], special_tokens=True)
    if cfg.mm_use_im_start_end:
        tokenizer.add_tokens(
            [constants.DEFAULT_EV_START_TOKEN, constants.DEFAULT_EV_END_TOKEN],
            special_tokens=True,
        )
    if len(tokenizer) > cfg.llama.vocab_size:
        params["llama"] = resize_token_embeddings(params["llama"], len(tokenizer))
    from eventgpt_tpu.models import eventchat, llama as llama_mod

    # Fusing and quantization are the dense decoder's transforms.
    eventchat.refuse_unserved(cfg, **{
        "--quant": args.quant != "none",
        "--fuse_params": getattr(args, "fuse_params", False)})
    if eventchat.decoder_of(cfg) is llama_mod:
        params["llama"] = _fuse_and_quantize(params["llama"], args)
    import jax.numpy as jnp

    jdt = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    if mesh is not None:
        from eventgpt_tpu.parallel.serving import shard_params_for_serving

        # Host tree -> sharded placement directly: a 7B load never
        # materializes an unsharded copy in HBM.
        params = shard_params_for_serving(params, cfg, mesh, dtype=jdt)
    else:
        params = place_params(params, jdt)
    # Memory ledger (ISSUE 9): the weight tree is device-resident from
    # here on — attribute it at the load boundary so every CLI (infer/
    # eval/serve) accounts it, not just the batcher (which registers
    # the same tree under the same identity — a no-op resize).
    from eventgpt_tpu.obs import memory as obs_memory

    obs_memory.LEDGER.register(
        "weights", f"shared/params-{id(params):x}",
        obs_memory.params_bytes(params))
    return cfg, params


def serving_mesh_from_args(args):
    """Mesh from --mesh_* flags; None for the single-chip fast path."""
    from eventgpt_tpu.parallel.serving import build_serving_mesh

    return build_serving_mesh(
        data=getattr(args, "mesh_data", 1),
        fsdp=getattr(args, "mesh_fsdp", 1),
        model=getattr(args, "mesh_model", 1),
    )


def main(argv=None) -> str:
    args = build_parser().parse_args(argv)
    if args.num_beams < 1:
        raise ValueError(f"num_beams must be >= 1, got {args.num_beams}")
    if args.draft_head is not None and not args.speculative:
        # Loading heads without a verify window would silently run plain
        # decode — the user would attribute plain-decode numbers to the
        # trained heads.
        raise ValueError(
            "--draft_head requires --speculative K > 0 (the heads draft "
            "into the K-token verification window)"
        )
    from eventgpt_tpu.models.medusa import load_medusa as _load_medusa
    from eventgpt_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    t0 = time.perf_counter()
    cfg, params, tokenizer = load_model(
        args.model_path, args.dtype, args.attn_impl, args.tokenizer_path,
        quant=args.quant, fuse=args.fuse_params,
    )
    # One mesh per run: params, activations, and the KV cache must all be
    # placed against the same Mesh object.
    mesh = serving_mesh_from_args(args)
    cfg, params = prepare_model(cfg, params, tokenizer, args, mesh=mesh)
    t_load = time.perf_counter() - t0

    t0 = time.perf_counter()
    prompt = prepare_event_prompt(args.query, args.conv_mode)
    _, pixels = process_event_file(
        args.event_frame, cfg.num_event_frames, cfg.vision.image_size
    )
    input_ids = tokenize_with_event(prompt, tokenizer)
    t_prep = time.perf_counter() - t0

    t0 = time.perf_counter()
    out_ids = eventchat.generate(
        params, cfg,
        [input_ids], pixels[None],
        max_new_tokens=args.max_new_tokens,
        temperature=args.temperature,
        top_p=args.top_p,
        eos_token_id=getattr(tokenizer, "eos_token_id", None),
        seed=args.seed,
        max_context=args.context_len,
        num_beams=args.num_beams,
        kv_quant=args.kv_cache == "int8",
        mesh=mesh,
        speculative=args.speculative,
        draft_head=(None if args.draft_head is None else
                    _load_medusa(args.draft_head)),
    )[0]
    t_gen = time.perf_counter() - t0

    output = tokenizer.batch_decode([out_ids], skip_special_tokens=True)[0].strip()
    if args.timing:
        import sys

        n = max(len(out_ids), 1)
        print(
            f"[timing] load={t_load:.2f}s prep={t_prep:.2f}s generate={t_gen:.2f}s "
            f"({n} tokens, {n / t_gen:.2f} tok/s)",
            file=sys.stderr,
        )
    print(output)
    return output


if __name__ == "__main__":
    main()
