"""Unified typed configuration for the whole framework.

The reference scatters configuration across four mechanisms (argparse CLI,
HF config JSON with ad-hoc fields, HfArgumentParser dataclasses in the
training pyc, and C++ YAML — SURVEY.md §5 "Config / flag system"). Here there
is exactly one: frozen dataclasses, composable, JSON round-trippable, with a
converter from HF-style ``config.json`` dicts for checkpoint interop
(custom fields ``mm_visual_tower`` / ``event_feature_adaptor`` /
``use_event_qformer`` per ``model/EventChatModel.py:71-81``).
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Optional

from eventgpt_tpu import constants


@dataclass(frozen=True)
class VisionConfig:
    """CLIP ViT vision tower (reference: CLIP ViT-L/14-336, README.md:173-177)."""

    hidden_size: int = 1024
    intermediate_size: int = 4096
    num_layers: int = 24
    num_heads: int = 16
    image_size: int = 336
    patch_size: int = 14
    num_channels: int = 3
    layer_norm_eps: float = 1e-5
    # "quick_gelu" is CLIP's activation; kept configurable for other towers.
    hidden_act: str = "quick_gelu"

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def num_tokens(self) -> int:
        # +1 for the CLS token; ViT-L/14-336 -> 577.
        return self.num_patches + 1

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


@dataclass(frozen=True)
class LlamaConfig:
    """LLaMA/Vicuna decoder-only LM."""

    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: Optional[int] = None
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 2048  # reference context cap: model/EventChatModel.py:378
    tie_word_embeddings: bool = False
    # "dense" = materialized-scores attention; "flash" = Pallas fused kernel
    # for prefill (ops/flash_attention.py); "ring" / "ulysses" = sequence-
    # parallel attention over a context>1 mesh (parallel/ring.py,
    # parallel/ulysses.py). Decode always uses the dense single-query path
    # against the KV cache.
    attn_impl: str = "dense"
    # Rematerialize each layer in the backward pass (jax.checkpoint around
    # the scan body). Identity for forward-only jit; under grad it stops AD
    # from stacking per-layer residuals — without it a 7B train step saves
    # full dequantized/flash-residual copies of the weight set (measured
    # 16.9G of HLO temps on v5e) and cannot fit one chip.
    remat: bool = True
    # Remat POLICY (ISSUE 13 satellite, VERDICT r5 / ROADMAP item 4's
    # enabler): what jax.checkpoint may SAVE instead of recomputing in
    # the backward pass. "full" = save nothing, recompute everything
    # (the pre-sweep behavior; jax's default policy, so it is
    # operationally identical to "nothing_saveable" — kept as two
    # spellings because the sweep reports the literal policy it ran).
    # "dots_saveable" saves matmul outputs — the middle ground between
    # full remat's ~19 TFLOP/step of recompute at 7B stage-2 and
    # remat-off's OOM. Only meaningful under grad with remat=True.
    remat_policy: str = "full"

    _ATTN_IMPLS = ("dense", "flash", "ring", "ulysses")
    _REMAT_POLICIES = ("full", "nothing_saveable", "dots_saveable",
                       "dots_with_no_batch_dims_saveable")

    def __post_init__(self):
        if self.remat_policy not in self._REMAT_POLICIES:
            # llama.prefill maps this string onto jax.checkpoint_policies;
            # a typo would silently fall back to full remat and the sweep
            # would report a policy it never ran.
            raise ValueError(
                f"remat_policy must be one of {self._REMAT_POLICIES}, "
                f"got {self.remat_policy!r}"
            )
        if self.attn_impl not in self._ATTN_IMPLS:
            # llama.prefill dispatches on this string and treats anything
            # unrecognized as dense — a typo would silently drop flash or
            # sequence parallelism instead of failing.
            raise ValueError(
                f"attn_impl must be one of {self._ATTN_IMPLS}, "
                f"got {self.attn_impl!r}"
            )

    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.hidden_size // self.num_heads

    @staticmethod
    def llama_7b() -> "LlamaConfig":
        # Flash prefill by default: measured 4.5x over dense at S=640 on
        # v5e (r05 chip run); decode still uses the single-query dense path.
        return LlamaConfig(attn_impl="flash")

    @staticmethod
    def llama_13b() -> "LlamaConfig":
        return LlamaConfig(
            hidden_size=5120, intermediate_size=13824, num_layers=40,
            num_heads=40, num_kv_heads=40, attn_impl="flash",
        )

    @staticmethod
    def tiny(vocab_size: int = 256) -> "LlamaConfig":
        """Small config for tests / CPU-mesh dry runs."""
        return LlamaConfig(
            vocab_size=vocab_size, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=256,
        )


@dataclass(frozen=True)
class HybridConfig:
    """A decoder whose blocks are of several kinds (``models/nemotron_h.py``):
    each block is ``x + mixer(rmsnorm(x))`` with exactly one mixer, chosen
    by its character of ``pattern``: ``M`` a Mamba-2 state-space mixer,
    ``E`` sparse experts in a latent space beside one shared expert, ``*``
    GQA attention without positional embedding and without an MLP. Field
    names follow the published ``nemotron_h`` ``config.json``.

    ``n_routed_experts`` is the router's width (every expert of the
    deployment); this process holds ``experts_held`` of them from
    ``experts_offset`` on, computes their part of the result and drops what
    the absent ones would add (expert parallelism without its exchange)."""

    pattern: str = "ME*"
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: Optional[int] = 128
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 4096
    attn_impl: str = "dense"
    # Mamba-2 mixer.
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    # Latent sparse experts.
    n_routed_experts: int = 512
    experts_held: int = 512
    experts_offset: int = 0
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True

    _KINDS = "ME*"

    def __post_init__(self):
        if not self.pattern or set(self.pattern) - set(self._KINDS):
            raise ValueError(
                f"pattern {self.pattern!r}: one of 'M', 'E', '*' a block")
        if self.attn_impl not in ("dense", "flash"):
            raise ValueError(
                f"attn_impl must be 'dense' or 'flash', got {self.attn_impl!r}")
        if not (0 <= self.experts_offset
                and self.experts_offset + self.experts_held
                <= self.n_routed_experts):
            raise ValueError(
                f"experts {self.experts_offset}..+{self.experts_held} are "
                f"not among the router's {self.n_routed_experts}")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("mamba_num_heads must divide by n_groups")

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    def resolved_head_dim(self) -> int:
        return (self.head_dim if self.head_dim is not None
                else self.hidden_size // self.num_heads)

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        """x | B | C, the channels the causal convolution runs over."""
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size


@dataclass(frozen=True)
class AfmoeConfig:
    """A decoder of window and global attention layers over sparse experts
    (``models/afmoe.py``). Field names follow the published ``afmoe``
    ``config.json`` (Arcee Trinity). Layer ``i`` attends within
    ``sliding_window`` positions and rotates queries and keys where
    ``layer_types[i]`` is ``sliding_attention``; where it is
    ``full_attention`` it sees every earlier position and applies no
    positional embedding. The first ``num_dense_layers`` layers carry a
    dense SwiGLU MLP of ``intermediate_size``, the others ``num_experts``
    routed SwiGLU experts of ``moe_intermediate_size`` beside one shared
    expert.

    ``num_experts`` is the router's width (every expert of the deployment);
    this process holds ``experts_held`` of them from ``experts_offset`` on,
    as ``HybridConfig`` does."""

    layer_types: tuple = ("sliding_attention", "full_attention")
    sliding_window: int = 4096
    num_dense_layers: int = 1
    vocab_size: int = 32000
    hidden_size: int = 3072
    intermediate_size: int = 12288
    moe_intermediate_size: int = 3072
    num_heads: int = 48
    num_kv_heads: int = 8
    head_dim: Optional[int] = 128
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 4096
    attn_impl: str = "dense"
    num_experts: int = 256
    experts_held: int = 256
    experts_offset: int = 0
    num_experts_per_tok: int = 4
    route_norm: bool = True
    route_scale: float = 1.0
    mup_enabled: bool = True

    _KINDS = ("sliding_attention", "full_attention")

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if not self.layer_types or set(self.layer_types) - set(self._KINDS):
            raise ValueError(
                f"layer_types {self.layer_types!r}: each one of {self._KINDS}")
        if self.attn_impl not in ("dense", "flash"):
            raise ValueError(
                f"attn_impl must be 'dense' or 'flash', got {self.attn_impl!r}")
        if not 0 <= self.num_dense_layers <= len(self.layer_types):
            raise ValueError(
                f"num_dense_layers {self.num_dense_layers} of "
                f"{len(self.layer_types)} layers")
        if self.sliding_window < 1:
            raise ValueError(f"sliding_window {self.sliding_window}")
        if not (0 <= self.experts_offset
                and self.experts_offset + self.experts_held
                <= self.num_experts):
            raise ValueError(
                f"experts {self.experts_offset}..+{self.experts_held} are "
                f"not among the router's {self.num_experts}")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    def count(self, kind: str) -> int:
        return self.layer_types.count(kind)

    def resolved_head_dim(self) -> int:
        return (self.head_dim if self.head_dim is not None
                else self.hidden_size // self.num_heads)


@dataclass(frozen=True)
class ProjectorConfig:
    """Event-feature -> LM-embedding projection stack.

    Mirrors the reference stack: MLP(1024->4096, GELU, 4096->4096) projector
    (``model/EventChatModel.py:87-93``, mlp_depth=2 at ``:67``) plus an optional
    Linear(4096->4096) feature adaptor (``model/EventChatModel.py:75-76``).
    """

    input_dim: int = 1024
    output_dim: int = 4096
    mlp_depth: int = 2
    use_feature_adaptor: bool = True


@dataclass(frozen=True)
class QFormerConfig:
    """Shape of the config-gated event Q-Former (``models/qformer.py``).

    The reference declares the module (``use_event_qformer``,
    ``model/EventChatModel.py:78-81``) but never ships its builder; all
    dims here are this framework's own design."""

    num_queries: int = 32
    num_layers: int = 2
    num_heads: int = 8
    hidden_size: int = 4096   # = LM embedding dim (queries live in LM space)
    mlp_ratio: int = 4

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


@dataclass(frozen=True)
class MeshConfig:
    """Logical device mesh for pjit sharding (SURVEY.md §2.4).

    Axes: ``data`` (pure DP), ``fsdp`` (ZeRO-style param sharding),
    ``model`` (tensor parallel). A ``context`` axis for ring-attention
    sequence parallelism is carved out of ``data`` when ``context > 1``.
    """

    data: int = 1
    fsdp: int = 1
    model: int = 1
    context: int = 1

    @property
    def num_devices(self) -> int:
        return self.data * self.fsdp * self.model * self.context


@dataclass(frozen=True)
class EventChatConfig:
    """Top-level multimodal model config (EventChat_llama equivalent)."""

    vision: VisionConfig = field(default_factory=VisionConfig)
    # The decoder behind the tower: dense (LlamaConfig), hybrid
    # (HybridConfig) or window / global attention over sparse experts
    # (AfmoeConfig); models/eventchat.decoder_of picks its module.
    llama: Any = field(default_factory=LlamaConfig)
    projector: ProjectorConfig = field(default_factory=ProjectorConfig)

    # Event pipeline envelope (common/common.py:114,118).
    num_event_frames: int = constants.DEFAULT_NUM_EVENT_FRAMES
    max_event_stream_us: int = constants.MAX_EVENT_STREAM_US
    # None -> num_temporal_tokens == num frames (model/EventChatModel.py:24-25).
    num_temporal_tokens: Optional[int] = None
    # spatial_temporal_encoder flag of the training pyc (SURVEY.md §2.2);
    # False feeds raw per-frame patch tokens to the LM instead of pooling.
    use_spatio_temporal_pool: bool = True

    mm_use_im_start_end: bool = False
    mm_use_im_patch_token: bool = True

    # use_event_qformer gate (model/EventChatModel.py:78-81): the reference
    # declares this path but never ships the builder (SURVEY.md §2.1 P6c);
    # models/qformer.py supplies the TPU-native design. When enabled, the
    # Q-Former's learned queries replace the spatio-temporal pool as the
    # LM's event tokens.
    use_event_qformer: bool = False
    qformer: QFormerConfig = field(default_factory=QFormerConfig)

    @property
    def num_event_tokens(self) -> int:
        """Tokens contributed by one event clip after the encode stage."""
        if self.use_event_qformer:
            return self.qformer.num_queries
        if not self.use_spatio_temporal_pool:
            return self.num_event_frames * self.vision.num_tokens
        t = self.num_temporal_tokens if self.num_temporal_tokens is not None else self.num_event_frames
        return t + self.vision.num_tokens  # 5 + 577 = 582 for defaults

    @staticmethod
    def eventgpt_7b() -> "EventChatConfig":
        return EventChatConfig(llama=LlamaConfig.llama_7b())

    @staticmethod
    def eventgpt_13b() -> "EventChatConfig":
        return EventChatConfig(
            llama=LlamaConfig.llama_13b(),
            projector=ProjectorConfig(output_dim=5120),
        )

    @staticmethod
    def tiny(vocab_size: int = 256) -> "EventChatConfig":
        """Tiny end-to-end config for tests: real structure, toy dims."""
        vision = VisionConfig(
            hidden_size=32, intermediate_size=64, num_layers=2, num_heads=4,
            image_size=28, patch_size=14,
        )
        llama = LlamaConfig.tiny(vocab_size)
        proj = ProjectorConfig(input_dim=32, output_dim=llama.hidden_size)
        return EventChatConfig(vision=vision, llama=llama, projector=proj)


# ---------------------------------------------------------------------------
# Serialization


def to_dict(cfg: Any) -> Any:
    if dataclasses.is_dataclass(cfg):
        return {f.name: to_dict(getattr(cfg, f.name)) for f in dataclasses.fields(cfg)}
    return cfg


_NESTED = {"vision": VisionConfig, "llama": LlamaConfig, "projector": ProjectorConfig,
           "qformer": QFormerConfig}


def event_chat_config_from_dict(data: dict) -> EventChatConfig:
    kwargs = {}
    for f in dataclasses.fields(EventChatConfig):
        if f.name not in data:
            continue
        v = data[f.name]
        if f.name == "llama" and isinstance(v, dict) and "pattern" in v:
            v = HybridConfig(**v)  # the decoder's kind, by what it states
        elif f.name == "llama" and isinstance(v, dict) and "layer_types" in v:
            v = AfmoeConfig(**v)
        elif f.name in _NESTED and isinstance(v, dict):
            v = _NESTED[f.name](**v)
        kwargs[f.name] = v
    return EventChatConfig(**kwargs)


def save_config(cfg: EventChatConfig, path: str) -> None:
    with open(path, "w") as f:
        json.dump(to_dict(cfg), f, indent=2)


def load_config(path: str) -> EventChatConfig:
    with open(path) as f:
        return event_chat_config_from_dict(json.load(f))


def default_attn_impl() -> str:
    """Flash prefill on TPU; dense where the CPU was asked for (the Pallas
    kernel only runs in slow interpret mode there). A backend that fails
    to initialise raises — it must not select the reference attention."""
    from eventgpt_tpu.utils.platform import backend_platform

    return "flash" if backend_platform() == "tpu" else "dense"


def from_hf_config(hf: dict, attn_impl: Optional[str] = None) -> EventChatConfig:
    """Build an EventChatConfig from an HF ``config.json`` dict.

    Understands stock LLaMA fields plus the reference's custom gating fields
    ``event_feature_adaptor`` / ``mm_use_im_start_end`` / ``mm_use_im_patch_token``
    (``model/EventChatModel.py:75``, ``inference.py:33-34``).
    ``attn_impl=None`` resolves per platform (``default_attn_impl``).
    ``model_type`` ``nemotron_h`` builds the hybrid decoder's configuration
    (``hybrid_from_hf``), ``afmoe`` the window / global decoder's
    (``afmoe_from_hf``); every other file a dense one.
    """
    attn_impl = attn_impl if attn_impl is not None else default_attn_impl()
    if hf.get("model_type") == "nemotron_h":
        llama = hybrid_from_hf(hf, attn_impl)
    elif hf.get("model_type") == "afmoe":
        llama = afmoe_from_hf(hf, attn_impl)
    else:
        llama = _dense_from_hf(hf, attn_impl)
    return _behind_the_tower(hf, llama)


def hybrid_from_hf(hf: dict, attn_impl: str) -> HybridConfig:
    """The published ``nemotron_h`` keys -> ``HybridConfig``. Depth: the
    first ``num_hidden_layers`` characters of ``hybrid_override_pattern``.
    A file that holds a share of the experts gives the count it holds under
    ``n_routed_experts`` and the router's width under
    ``published.n_routed_experts`` (``experts_offset``: where the share
    starts, 0 unless stated). The multi-token-prediction head
    (``num_nextn_predict_layers``) drafts and is not on the next-token path:
    it is not built."""
    depth = int(hf["num_hidden_layers"])
    pattern = str(hf["hybrid_override_pattern"])[:depth]
    if len(pattern) != depth:
        raise ValueError(
            f"hybrid_override_pattern has {len(pattern)} blocks, "
            f"num_hidden_layers asks for {depth}")
    held = int(hf["n_routed_experts"])
    width = int(hf.get("published", {}).get("n_routed_experts", held))
    if int(hf.get("n_group", 1)) != 1 or int(hf.get("topk_group", 1)) != 1:
        raise ValueError("group-limited routing (n_group, topk_group > 1) "
                         "is not implemented")
    if hf.get("mlp_hidden_act", "relu2") != "relu2":
        raise ValueError(f"mlp_hidden_act {hf['mlp_hidden_act']!r}: only relu2")
    return HybridConfig(
        pattern=pattern, attn_impl=attn_impl,
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf.get("head_dim"),
        rms_norm_eps=hf.get("layer_norm_epsilon", 1e-5),
        max_seq_len=hf.get("max_position_embeddings", 2048),
        mamba_num_heads=hf["mamba_num_heads"],
        mamba_head_dim=hf["mamba_head_dim"], n_groups=hf["n_groups"],
        ssm_state_size=hf["ssm_state_size"], conv_kernel=hf["conv_kernel"],
        chunk_size=hf["chunk_size"],
        n_routed_experts=width, experts_held=held,
        experts_offset=int(hf.get("experts_offset", 0)),
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        moe_latent_size=hf["moe_latent_size"],
        moe_shared_expert_intermediate_size=hf[
            "moe_shared_expert_intermediate_size"],
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
    )


def afmoe_from_hf(hf: dict, attn_impl: str) -> AfmoeConfig:
    """The published ``afmoe`` keys -> ``AfmoeConfig``. Depth: a file cut in
    depth names the published layers it keeps under ``layers_kept`` (indices
    into ``layer_types``, ``num_hidden_layers`` of them); without it, the
    first ``num_hidden_layers`` entries. The first ``num_dense_layers`` of
    the kept layers are dense. A file that holds a share of the experts
    gives the count it holds under ``num_experts`` and the router's width
    under ``published.num_experts`` (``experts_offset``: where the share
    starts, 0 unless stated)."""
    depth = int(hf["num_hidden_layers"])
    kept = hf.get("layers_kept", range(depth))
    types = tuple(hf["layer_types"][int(i)] for i in kept)
    if len(types) != depth:
        raise ValueError(f"{len(types)} layers kept, num_hidden_layers "
                         f"asks for {depth}")
    for key in ("n_group", "topk_group", "num_expert_groups",
                "num_limited_groups"):
        if int(hf.get(key, 1)) != 1:
            raise ValueError(f"group-limited routing ({key} > 1) is not "
                             f"implemented")
    if hf.get("score_func", "sigmoid") != "sigmoid":
        raise ValueError(f"score_func {hf['score_func']!r}: only sigmoid")
    if hf.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {hf['hidden_act']!r}: only silu")
    if int(hf.get("num_shared_experts", 1)) != 1:
        raise ValueError("num_shared_experts: one shared expert only")
    if hf.get("rope_scaling") is not None:
        raise ValueError("rope_scaling is not implemented for afmoe")
    held = int(hf["num_experts"])
    return AfmoeConfig(
        layer_types=types, attn_impl=attn_impl,
        sliding_window=int(hf["sliding_window"]),
        num_dense_layers=int(hf["num_dense_layers"]),
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf.get("head_dim"),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
        max_seq_len=hf.get("max_position_embeddings", 2048),
        num_experts=int(hf.get("published", {}).get("num_experts", held)),
        experts_held=held, experts_offset=int(hf.get("experts_offset", 0)),
        num_experts_per_tok=hf["num_experts_per_tok"],
        route_norm=bool(hf.get("route_norm", True)),
        route_scale=float(hf.get("route_scale", 1.0)),
        mup_enabled=bool(hf.get("mup_enabled", False)),
    )


def _dense_from_hf(hf: dict, attn_impl: str) -> LlamaConfig:
    return LlamaConfig(
        attn_impl=attn_impl,
        vocab_size=hf.get("vocab_size", 32000),
        hidden_size=hf.get("hidden_size", 4096),
        intermediate_size=hf.get("intermediate_size", 11008),
        num_layers=hf.get("num_hidden_layers", 32),
        num_heads=hf.get("num_attention_heads", 32),
        num_kv_heads=hf.get("num_key_value_heads", hf.get("num_attention_heads", 32)),
        rope_theta=hf.get("rope_theta", 10000.0),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
        max_seq_len=hf.get("max_position_embeddings", 2048),
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
    )


def _behind_the_tower(hf: dict, llama) -> EventChatConfig:
    # The reference identifies its tower by name only (``mm_visual_tower`` ->
    # CLIP ViT-L/14-336, README.md:173-177); an explicit "vision_config" dict
    # (this framework's extension, written by its own config exports)
    # overrides the dims — e.g. tiny synthetic checkpoints in tests.
    if isinstance(hf.get("vision_config"), dict):
        # Filter to known fields: HF-style vision_config dicts carry foreign
        # keys (model_type, projection_dim, ...) that must not crash the load.
        known = {f.name for f in dataclasses.fields(VisionConfig)}
        vision = VisionConfig(
            **{k: v for k, v in hf["vision_config"].items() if k in known}
        )
    else:
        vision = VisionConfig()
    # Presence of the key — not its value — gates the adaptor, matching the
    # reference's hasattr() check at model/EventChatModel.py:75-76.
    proj = ProjectorConfig(
        input_dim=vision.hidden_size,
        output_dim=llama.hidden_size,
        mlp_depth=hf.get("mm_projector_depth", 2),
        use_feature_adaptor="event_feature_adaptor" in hf,
    )
    # Value-respecting gate: a parsed config.json dict contains explicit
    # false values (unlike the reference's hasattr check on a config object,
    # model/EventChatModel.py:77), so presence alone must not enable it.
    qf_kwargs = {}
    if isinstance(hf.get("qformer_config"), dict):
        known_qf = {f.name for f in dataclasses.fields(QFormerConfig)}
        qf_kwargs = {k: v for k, v in hf["qformer_config"].items() if k in known_qf}
    return EventChatConfig(
        vision=vision,
        llama=llama,
        projector=proj,
        use_spatio_temporal_pool=hf.get("spatial_temporal_encoder", True),
        use_event_qformer=bool(hf.get("use_event_qformer", False)),
        qformer=QFormerConfig(hidden_size=llama.hidden_size, **{k: v for k, v in qf_kwargs.items() if k != "hidden_size"}),
        mm_use_im_start_end=hf.get("mm_use_im_start_end", False),
        mm_use_im_patch_token=hf.get("mm_use_im_patch_token", True),
    )
