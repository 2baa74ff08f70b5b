"""Mesh-sharded serving: parameter + KV-cache placement for ``generate``.

The reference serves its frozen LLM on one GPU — torch module + HF generate
(``inference.py:28-66``, ``model/EventChatModel.py:237-276``). The BASELINE
north star is the same surface over a pod: HF weights loaded into a
pjit-sharded FSDP/TP layout with the KV cache resident in HBM. This module
is the serving half of ``parallel/sharding.py``: it places an EventChat
param tree (plain, int8 or LoRA-composite leaves) and a KV cache onto
a ``Mesh`` so the existing jit'd prefill/decode units compile to one SPMD
program — computation follows data, XLA inserts the collectives (fsdp
all-gathers, model-axis psums).

Layout decisions specific to serving:

  * Params reuse the training specs (``eventchat_param_specs``): matmul
    contraction dims over ``fsdp`` (ZeRO-style, gathered at use), head /
    column dims over ``model`` (megatron TP, one psum per layer).
  * Quantized leaves shard their int payload exactly like the bf16 weight
    they replace; the per-channel scales replicate over the contraction
    axis (they are 1/256th of the payload — sharding them buys nothing and
    the size-1 / group dims do not always divide the axis).
  * The KV cache shards batch over whatever prefix of ``(data, fsdp)``
    divides the run's batch (pure-TP fallback for batch 1) and KV heads
    over ``model`` — decode reads the cache in place, no resharding per
    step.
  * ``context`` must be 1: sequence parallelism is a prefill-side
    optimization (ring/ulysses in ``parallel/ring.py``/``ulysses.py``)
    whose value is long-context *training*; serving prompts sit far below
    the 2048 context cap and the decode hot loop attends to the whole
    cache from a single query token.
  * The stall-free-admission lane buffers (ISSUE 5: the resident
    (K_cap, S_lane) lane KV cache and (K_cap, S_lane, D) prompt-embed
    buffer that mixed segments advance) place through the SAME helpers —
    ``shard_kv_cache`` at batch K_cap and ``shard_batch_array`` — and
    the mixed-segment jits (``serve._get_sharded_mixed_*``) pin their
    lane outputs to that placement, so the donated lane buffers keep
    aliasing across boundaries exactly like the resident decode cache.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from eventgpt_tpu.ops import quant as quant_mod
from eventgpt_tpu.parallel.sharding import eventchat_param_specs


def _scale_spec(spec: P) -> P:
    """Spec for a quantization-scale leaf: same rank as the weight spec with
    the contraction (second-to-last) axis replicated — int8 scales have a
    size-1 dim there."""
    parts = list(spec) + [None] * 0
    if len(parts) >= 2:
        parts[-2] = None
    return P(*parts)


def _put(x, mesh: Mesh, spec: P, dtype=None):
    arr = jnp.asarray(x) if dtype is None else jnp.asarray(x, dtype)
    return jax.device_put(arr, NamedSharding(mesh, spec))


def _shard_tree(tree: Any, spec: Any, mesh: Mesh, dtype) -> Any:
    """Recursive quant-aware placement. ``spec`` mirrors ``tree`` except at
    composite leaves ({"q","s"} / {"w","a","b"}), where one
    PartitionSpec covers the whole composite."""
    if quant_mod.is_quantized(tree):
        return {"q": _put(tree["q"], mesh, spec),
                "s": _put(tree["s"], mesh, _scale_spec(spec), jnp.float32)}
    if quant_mod.is_lora(tree):
        rep = P(*([None] * (len(spec) if spec else 0)))
        return {"w": _shard_tree(tree["w"], spec, mesh, dtype),
                "a": _put(tree["a"], mesh, rep, dtype),
                "b": _put(tree["b"], mesh, rep, dtype)}
    if isinstance(tree, dict):
        return {k: _shard_tree(v, spec[k], mesh, dtype) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(
            _shard_tree(v, s, mesh, dtype) for v, s in zip(tree, spec)
        )
    return _put(tree, mesh, spec, dtype)


def shard_params_for_serving(
    params: Any,
    cfg,
    mesh: Mesh,
    dtype=None,
) -> Any:
    """Place an EventChat param tree on ``mesh`` under the serving layout.

    Accepts host (numpy) or device trees — host trees go straight to their
    sharded placement, so a 7B load never materializes an unsharded copy in
    HBM. ``dtype`` casts float leaves (quantized payloads/scales keep their
    storage types).
    """
    _require_serving_mesh(mesh)
    specs = eventchat_param_specs(
        cfg.projector.use_feature_adaptor,
        cfg.projector.mlp_depth,
        use_qformer="qformer" in params,
    )
    from eventgpt_tpu.parallel.sharding import vocab_safe_llama_specs

    emb = params["llama"]["embed_tokens"]
    vocab = int((emb["q"] if isinstance(emb, dict) else emb).shape[0])
    vocab_safe_llama_specs(specs["llama"], vocab, mesh)
    _adapt_fused_llama_specs(specs["llama"], params["llama"])
    return {k: _shard_tree(v, specs[k], mesh, dtype) for k, v in params.items()}


def _adapt_fused_llama_specs(llama_specs: Any, llama_params: Any) -> None:
    """``fuse_llama_params`` merges q|k|v and gate|up leaves; the fused
    column dim shards over ``model`` exactly like the unfused columns did
    (GSPMD reshards the post-matmul slice boundaries as needed)."""
    attn = llama_params["layers"]["attn"]
    if "qkv" in attn:
        llama_specs["layers"]["attn"] = {
            "qkv": P(None, "fsdp", "model"),
            "o": P(None, "model", "fsdp"),
        }
    if "gate_up" in llama_params["layers"]["mlp"]:
        llama_specs["layers"]["mlp"] = {
            "gate_up": P(None, "fsdp", "model"),
            "down": P(None, "model", "fsdp"),
        }


def _require_serving_mesh(mesh: Mesh) -> None:
    if "context" in mesh.shape and mesh.shape["context"] > 1:
        raise ValueError(
            "serving meshes must have context=1 (sequence parallelism is a "
            "long-context training optimization; decode attends to the full "
            "cache from one query token)"
        )


def serving_divisors(num_kv_heads: int, mesh_shape, batch: int) -> dict:
    """Per-device byte divisors of the serving layout, as pure
    arithmetic on a ``{axis: size}`` mapping — THE sharding rules of
    this module, exported for the memory ledger's capacity model
    (``obs.memory.estimate``), which must fit-check a pod config
    without building a Mesh or materializing a weight:

      * ``batch``: the largest prefix of ``(data, fsdp)`` whose size
        product divides the batch (``serving_batch_axes``);
      * ``kv_heads``: ``model`` when it divides the KV head count
        (``shard_kv_cache`` / ``prefix_block_sharding``);
      * ``weights``: ``fsdp × model`` (``eventchat_param_specs``:
        contraction dims over fsdp, head/column dims over model —
        scales/norms replicate, a rounding the estimate absorbs).
    """
    batch_div = 1
    for ax in ("data", "fsdp"):
        n = int(mesh_shape.get(ax, 1))
        if n > 1 and batch % (batch_div * n) == 0:
            batch_div *= n
    model_n = int(mesh_shape.get("model", 1))
    head_div = model_n if model_n > 1 and num_kv_heads % model_n == 0 else 1
    return {"batch": batch_div, "kv_heads": head_div,
            "weights": int(mesh_shape.get("fsdp", 1)) * model_n}


def serving_batch_axes(mesh: Mesh, batch: int) -> Tuple[str, ...]:
    """Largest prefix of ``(data, fsdp)`` whose size product divides
    ``batch`` — batch 1 on a wide mesh degrades to pure TP + weight
    gathering instead of failing on an unshardable batch dim."""
    axes = []
    prod = 1
    for ax in ("data", "fsdp"):
        n = mesh.shape.get(ax, 1)
        if n > 1 and batch % (prod * n) == 0:
            axes.append(ax)
            prod *= n
    return tuple(axes)


def batch_sharding(mesh: Mesh, batch: int, ndim: int) -> NamedSharding:
    axes = serving_batch_axes(mesh, batch)
    return NamedSharding(mesh, P(axes if axes else None, *([None] * (ndim - 1))))


def shard_batch_array(x, mesh: Mesh, dtype=None):
    """Place a (B, ...) activation with batch over the serving batch axes."""
    arr = jnp.asarray(x) if dtype is None else jnp.asarray(x, dtype)
    return jax.device_put(arr, batch_sharding(mesh, arr.shape[0], arr.ndim))


def replicate(x, mesh: Mesh):
    arr = jnp.asarray(x)
    return jax.device_put(arr, NamedSharding(mesh, P(*([None] * arr.ndim))))


def place_carry(mesh: Mesh, batch: int, frozen, n_rem, base_pos=None):
    """Place the pipelined scheduler's (frozen, n_rem, base_pos) control
    carry on the serving batch axes — the same placement the segment jits
    pin for their carry OUTPUTS, so a host-rebuilt carry (after an
    admission or forced finish) feeds the next dispatch without a
    reshard. ``base_pos`` may be None (plain decode has no gather base)."""
    sh = NamedSharding(mesh, P(serving_batch_axes(mesh, batch) or None))
    put = lambda x: None if x is None else jax.device_put(jnp.asarray(x), sh)
    return put(frozen), put(n_rem), put(base_pos)


def prefix_block_sharding(mesh: Mesh, cfg) -> NamedSharding:
    """Placement of one prefix-KV cache ENTRY block (L, 1, S, KV, hd):
    KV heads over ``model`` exactly like the resident cache (so the
    entry copy at admission — ``serve._prefix_prefill`` reading it, and
    ``serve._slice_prefix_block`` producing it on insert-on-prefill — is
    a local dynamic-slice/update per shard, no resharding), everything
    else replicated: the batch dim is 1, so the (data, fsdp) batch axes
    drop out. The int8-KV scale plane shares the spec (its trailing dim
    is 1; the head axis still divides)."""
    model_n = mesh.shape.get("model", 1)
    head_ax = ("model"
               if model_n > 1 and cfg.num_kv_heads % model_n == 0 else None)
    return NamedSharding(mesh, P(None, None, None, head_ax, None))


def shard_kv_cache(cache: Any, cfg, mesh: Mesh) -> Any:
    """Place a fresh KV cache: (L, B, S, KV, hd) with batch over the serving
    batch axes and KV heads over ``model`` (skipped if it does not divide
    the head count). ``length`` (B,) shards with the batch.

    Paged caches (ISSUE 12, ``"bt"`` present): the arena has NO batch
    axis — which row owns which block is host bookkeeping, so any device
    may need any block — and therefore replicates over the batch axes;
    only the KV-head axis shards over ``model`` (the same per-device
    divisor as the dense cache's head split). The block table and length
    planes shard with the batch like every per-row carry. This trades
    the dense layout's batch-axis KV split for block-granular
    allocation; recovering a sharded arena (blocks over (data, fsdp)
    with placement-aware tables) is the item-1b handoff seam
    (DISTRIBUTED.md)."""
    quant = isinstance(cache["k"], dict)
    if "bt" in cache:
        batch = int(cache["bt"].shape[0])
        baxes = serving_batch_axes(mesh, batch)
        bspec = baxes if baxes else None
        model_n = mesh.shape.get("model", 1)
        head_ax = ("model" if (model_n > 1
                               and cfg.num_kv_heads % model_n == 0) else None)
        pool_spec = P(None, None, None, head_ax, None)

        def put_pool(buf):
            if isinstance(buf, dict):
                return {"q": _put(buf["q"], mesh, pool_spec),
                        "s": _put(buf["s"], mesh, pool_spec)}
            return _put(buf, mesh, pool_spec)

        return {
            "k": put_pool(cache["k"]),
            "v": put_pool(cache["v"]),
            "bt": _put(cache["bt"], mesh, P(bspec, None)),
            "length": _put(cache["length"], mesh, P(bspec)),
        }
    batch = int(
        (cache["k"]["q"] if quant else cache["k"]).shape[1]
    )
    baxes = serving_batch_axes(mesh, batch)
    bspec = baxes if baxes else None
    model_n = mesh.shape.get("model", 1)
    head_ax = "model" if (model_n > 1 and cfg.num_kv_heads % model_n == 0) else None
    buf_spec = P(None, bspec, None, head_ax, None)

    def put_buf(buf):
        if isinstance(buf, dict):
            return {"q": _put(buf["q"], mesh, buf_spec),
                    "s": _put(buf["s"], mesh, buf_spec)}
        return _put(buf, mesh, buf_spec)

    return {
        "k": put_buf(cache["k"]),
        "v": put_buf(cache["v"]),
        "length": _put(cache["length"], mesh, P(bspec)),
    }


def require_flash_heads_divide(llama_cfg, mesh: Mesh) -> None:
    """Flash under a serving mesh runs per-shard with heads over ``model``
    (``serving_flash_shard_map``), so the head count must divide that
    axis. The kernel does not give way to the dense reference quietly: a
    configuration it cannot serve is refused — by ``llama.prefill`` for
    every caller, and by the server already when it is built — with the
    choice left to the caller."""
    model_n = mesh.shape.get("model", 1)
    if llama_cfg.attn_impl == "flash" and llama_cfg.num_heads % model_n:
        raise ValueError(
            f"attn_impl='flash' under a serving mesh shards heads over "
            f"model: num_heads={llama_cfg.num_heads} must divide by "
            f"model={model_n}; choose another mesh, or set "
            f"attn_impl='dense' explicitly"
        )


def serving_flash_shard_map(mesh: Mesh, batch: int):
    """Pallas flash prefill under a serving mesh.

    The flash kernel is an opaque custom call to the SPMD partitioner, so a
    bare call inside the pjit'd prefill would force an all-gather of every
    operand. Wrapped in shard_map it runs fully locally instead: batch over
    the serving batch axes, heads over ``model`` — the same layout the
    surrounding qkv/o matmuls already produce, so no resharding happens at
    the boundary and sharded prefill keeps flash's O(S) memory instead of
    falling back to dense (B, H, T, T) scores. Sequence stays unsharded
    (serving meshes have context=1, ``_require_serving_mesh``); causality is
    therefore purely local. Callers hold ``require_flash_heads_divide``.

    Returns ``f(q, k, v, valid) -> out`` with q/k/v (B, S, H, hd) post-GQA
    repeat and valid (B, S) bool.
    """
    from jax.sharding import PartitionSpec as P

    from eventgpt_tpu.ops.flash_attention import flash_attention

    baxes = serving_batch_axes(mesh, batch)
    bspec = baxes if baxes else None
    head_ax = "model" if mesh.shape.get("model", 1) > 1 else None
    qkv_spec = P(bspec, None, head_ax, None)
    valid_spec = P(bspec, None)

    def local(q, k, v, valid):
        return flash_attention(q, k, v, valid=valid, causal=True)

    # check_vma=False: the pallas_call's out ShapeDtypeStruct carries no
    # varying-mesh-axes annotation, and the kernel is purely local anyway
    # (no collectives inside).
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, valid_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )


def build_serving_mesh(
    data: int = 1, fsdp: int = 1, model: int = 1,
    devices: Optional[list] = None,
) -> Optional[Mesh]:
    """CLI helper: mesh from --mesh_* flags; None when everything is 1
    (single-chip fast path, no resharding)."""
    if data * fsdp * model <= 1:
        return None
    from eventgpt_tpu.config import MeshConfig
    from eventgpt_tpu.parallel.mesh import make_mesh

    return make_mesh(
        MeshConfig(data=data, fsdp=fsdp, context=1, model=model),
        devices=devices,
    )
