"""The plain reference: what the served model should compute, written out.

float32 throughout, ``jax.default_matmul_precision("highest")``, no cache,
no kernel, no batching, one request at a time. It imports nothing of the
program: the event raster, the CLIP preprocessing, the prompt template and
byte tokenizer, the CLIP ViT tower, the projector, the spatio-temporal
pool, the splice at ``<event>`` and the GQA / RoPE / SwiGLU decoder are
all spelled out here from the published descriptions (CLIP ViT-L/14-336,
LLaVA-style projector, EventGPT's pool, Mistral / InternLM2 decoder
blocks). It reads the benchmark-made weights (``benchmark/weights.py``) by
the names the served tree uses, and multiplies int8 leaves out as
``q * s``.

``lower`` computes the same thing in a precision below the one the
configuration states, for the control (``benchmark/correct.py``):
``"int4"`` re-quantizes every decoder weight to 4 bits in groups of 128
rows.
"""

from __future__ import annotations

import functools
import io
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

SYSTEM = ("A chat between a curious human and an artificial intelligence "
          "assistant. The assistant gives helpful, detailed, and polite "
          "answers to the human's questions.")
CLIP_MEAN = np.array([0.48145466, 0.4578275, 0.40821073], np.float32)
CLIP_STD = np.array([0.26862954, 0.26130258, 0.27577711], np.float32)
N_FRAMES = 5
BOS, BYTE_OFFSET = 1, 3


# -- host side: stream -> pixels, question -> ids ----------------------------

def prompt_ids(question: str) -> Tuple[np.ndarray, np.ndarray]:
    """The single-turn prompt around the event block, as byte-level ids:
    (ids before the block, ids after it). Vicuna-v1 two-separator template;
    BOS once, at the start."""
    text = (f"{SYSTEM} USER: <ev_start><event><ev_end>\n{question} "
            f"ASSISTANT:")
    head, tail = text.split("<event>")
    enc = lambda s: [b + BYTE_OFFSET for b in s.encode("utf-8")]
    return (np.array([BOS] + enc(head), np.int32), np.array(enc(tail), np.int32))


def raster(x, y, p) -> np.ndarray:
    """One slice of events -> an (H, W, 3) uint8 frame: white background,
    the last event at a pixel wins, polarity 1 red, 0 blue; the frame is as
    large as the slice's own largest coordinates."""
    h, w = int(y.max()) + 1, int(x.max()) + 1
    frame = np.full((h, w, 3), 255, np.uint8)
    red, blue = np.array([255, 0, 0], np.uint8), np.array([0, 0, 255], np.uint8)
    # Later events overwrite earlier ones: keep each pixel's largest ordinal.
    lin = y.astype(np.int64) * w + x.astype(np.int64)
    last = np.full(h * w, -1, np.int64)
    np.maximum.at(last, lin, np.arange(lin.size))
    hit = last >= 0
    flat = frame.reshape(-1, 3)
    flat[hit] = np.where(p[last[hit]][:, None] != 0, red, blue)
    return frame


def clip_preprocess(frame: np.ndarray, size: int) -> np.ndarray:
    """CLIPImageProcessor: bicubic resize of the shortest edge to ``size``,
    centre crop, 1/255, normalise, CHW."""
    from PIL import Image

    img = Image.fromarray(frame)
    w, h = img.size
    if w <= h:
        nw, nh = size, int(size * h / w)
    else:
        nw, nh = int(size * w / h), size
    arr = np.asarray(img.resize((nw, nh), Image.Resampling.BICUBIC), np.float32)
    top, left = (nh - size) // 2, (nw - size) // 2
    arr = arr[top:top + size, left:left + size]
    arr = (arr / 255.0 - CLIP_MEAN) / CLIP_STD
    return np.transpose(arr, (2, 0, 1))


def pixels_from_npy(raw: bytes, size: int) -> np.ndarray:
    """An uploaded stream -> (5, 3, size, size) float32: five slices of equal
    event count (the last takes the remainder), each rastered and preprocessed."""
    ev = np.load(io.BytesIO(raw))
    n = len(ev)
    per = n // N_FRAMES
    frames = []
    for i in range(N_FRAMES):
        lo, hi = i * per, ((i + 1) * per if i < N_FRAMES - 1 else n)
        frames.append(clip_preprocess(
            raster(ev["x"][lo:hi], ev["y"][lo:hi], ev["p"][lo:hi]), size))
    return np.stack(frames)


# -- device side --------------------------------------------------------------

def _f32(x):
    if isinstance(x, dict) and "q" in x:  # an int8 leaf, multiplied out
        return x["q"].astype(jnp.float32) * x["s"].astype(jnp.float32)
    return x.astype(jnp.float32)


def _int4(w):
    """Symmetric 4-bit re-quantization in groups of 128 rows (the control)."""
    k, n = w.shape
    g = 128 if k % 128 == 0 else k
    wg = w.reshape(k // g, g, n)
    s = jnp.maximum(jnp.max(jnp.abs(wg), axis=1, keepdims=True), 1e-12) / 7.0
    return (jnp.clip(jnp.round(wg / s), -8, 7) * s).reshape(k, n)


def _layer_norm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * _f32(p["scale"]) + _f32(p["bias"])


def _rms_norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(w)


@functools.partial(jax.jit, static_argnames=("heads", "patch", "eps"))
def encode_events(clip, proj, pixels, *, heads: int, patch: int,
                  eps: float = 1e-5):
    """(5, 3, S, S) pixels -> (5 + tokens, D_lm) event tokens: CLIP ViT
    (pre-LN blocks, quick-GELU, last hidden state without the post-LN),
    projector MLP (exact GELU), feature adaptor, then temporal tokens (mean
    over space, one a frame) followed by spatial tokens (mean over frames)."""
    with jax.default_matmul_precision("highest"):
        t, c, s, _ = pixels.shape
        g = s // patch
        x = pixels.reshape(t, c, g, patch, g, patch)
        x = x.transpose(0, 2, 4, 1, 3, 5).reshape(t, g * g, c * patch * patch)
        emb = clip["embeddings"]
        x = x @ _f32(emb["patch_embedding"])
        cls = jnp.broadcast_to(_f32(emb["class_embedding"]), (t, 1, x.shape[-1]))
        x = jnp.concatenate([cls, x], 1) + _f32(emb["position_embedding"])
        x = _layer_norm(x, clip["pre_layernorm"], eps)
        d = x.shape[-1]
        hd = d // heads

        def block(x, layer):
            y = _layer_norm(x, layer["ln1"], eps)
            lin = lambda p, v: v @ _f32(p["kernel"]) + _f32(p["bias"])
            a = layer["attn"]
            q = lin(a["q"], y).reshape(t, -1, heads, hd) / math.sqrt(hd)
            k = lin(a["k"], y).reshape(t, -1, heads, hd)
            v = lin(a["v"], y).reshape(t, -1, heads, hd)
            pr = jax.nn.softmax(jnp.einsum("bqhd,bkhd->bhqk", q, k), -1)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", pr, v).reshape(t, -1, d)
            x = x + lin(a["o"], ctx)
            y = _layer_norm(x, layer["ln2"], eps)
            y = lin(layer["mlp"]["fc1"], y)
            y = y * jax.nn.sigmoid(1.702 * y)
            return x + lin(layer["mlp"]["fc2"], y), None

        x, _ = jax.lax.scan(block, x, clip["layers"])
        for i, layer in enumerate(proj["mlp"]):
            if i:
                x = jax.nn.gelu(x, approximate=False)
            x = x @ _f32(layer["kernel"]) + _f32(layer["bias"])
        if "adaptor" in proj:
            x = x @ _f32(proj["adaptor"]["kernel"]) + _f32(proj["adaptor"]["bias"])
        return jnp.concatenate([x.mean(1), x.mean(0)], 0)


@functools.partial(jax.jit,
                   static_argnames=("heads", "kv_heads", "theta", "eps", "lower"))
def decoder_logits(llama, embeds, rows, *, heads: int, kv_heads: int,
                   theta: float, eps: float, lower: Optional[str] = None):
    """(T, D) input embeddings -> float32 logits at positions ``rows``.
    Causal attention over all T positions, so padding after the last real
    position changes nothing before it."""
    if lower not in (None, "int4"):
        raise ValueError(f"lower {lower!r}: only int4")
    w_of = (lambda leaf: _int4(_f32(leaf))) if lower == "int4" else _f32
    with jax.default_matmul_precision("highest"):
        t, d = embeds.shape
        hd = d // heads
        inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
        ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
        cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
        sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]

        def rope(v):
            v1, v2 = v[..., : hd // 2], v[..., hd // 2:]
            return v * cos + jnp.concatenate([-v2, v1], -1) * sin

        causal = jnp.tril(jnp.ones((t, t), bool))

        def block(x, layer):
            a, m = layer["attn"], layer["mlp"]
            y = _rms_norm(x, layer["input_norm"], eps)
            q = rope((y @ w_of(a["q"])).reshape(t, heads, hd))
            k = rope((y @ w_of(a["k"])).reshape(t, kv_heads, hd))
            v = (y @ w_of(a["v"])).reshape(t, kv_heads, hd)
            rep = heads // kv_heads
            k = jnp.repeat(k, rep, axis=1)  # query head h reads kv head h // rep
            v = jnp.repeat(v, rep, axis=1)
            sc = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
            pr = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), -1)
            ctx = jnp.einsum("hqk,khd->qhd", pr, v).reshape(t, heads * hd)
            x = x + ctx @ w_of(a["o"])
            y = _rms_norm(x, layer["post_norm"], eps)
            gate, up = y @ w_of(m["gate"]), y @ w_of(m["up"])
            return x + (jax.nn.silu(gate) * up) @ w_of(m["down"]), None

        x, _ = jax.lax.scan(block, embeds, llama["layers"])
        x = _rms_norm(x[rows], llama["final_norm"], eps)
        return x @ w_of(llama["lm_head"])


def answer_logits(tree, widths: dict, raw_npy: bytes, question: str,
                  answer: np.ndarray, t_pad: int, a_pad: int,
                  lower: Optional[str] = None):
    """Reference logits (len(answer), V) for one served request: the logits
    that predict each served token, given the prompt and the served tokens
    before it."""
    pixels = pixels_from_npy(raw_npy, widths["image_size"])
    ev = encode_events(tree["clip"], tree["projector"], jnp.asarray(pixels),
                       heads=widths["clip_heads"], patch=widths["patch_size"])
    pre, post = prompt_ids(question)
    table = tree["llama"]["embed_tokens"]
    answer = np.asarray(answer, np.int32)
    emb = jnp.concatenate([
        _f32(table[jnp.asarray(pre)]), ev, _f32(table[jnp.asarray(post)]),
        _f32(table[jnp.asarray(answer[:-1])]) if len(answer) > 1
        else jnp.zeros((0, ev.shape[-1]), jnp.float32)], 0)
    n_prompt = len(pre) + ev.shape[0] + len(post)
    t = emb.shape[0]
    if t > t_pad or len(answer) > a_pad:
        raise ValueError(f"request of {t} positions / {len(answer)} tokens "
                         f"exceeds the reference's padding {t_pad} / {a_pad}")
    emb = jnp.pad(emb, ((0, t_pad - t), (0, 0)))
    rows = np.minimum(n_prompt - 1 + np.arange(a_pad), t_pad - 1)
    logits = decoder_logits(
        tree["llama"], emb, jnp.asarray(rows, jnp.int32),
        heads=widths["heads"], kv_heads=widths["kv_heads"],
        theta=float(widths["rope_theta"]), eps=float(widths["rms_norm_eps"]),
        lower=lower)
    return logits[: len(answer)], n_prompt


def widths_of(hf: dict) -> dict:
    """The sizes the reference needs, from the configuration file alone."""
    vc = hf.get("vision_config", {})
    return {
        "heads": hf["num_attention_heads"],
        "kv_heads": hf.get("num_key_value_heads", hf["num_attention_heads"]),
        "rope_theta": hf.get("rope_theta", 10000.0),
        "rms_norm_eps": hf.get("rms_norm_eps", 1e-5),
        "image_size": vc.get("image_size", 336),
        "patch_size": vc.get("patch_size", 14),
        "clip_heads": vc.get("num_heads", 16),
    }
