"""Operations and bytes the algorithm needs, from a configuration's sizes.

Every function takes the configuration file's HF-style dict (``hf``) and
counts what the mathematics requires: 2 FLOP a multiply-add, attention over
the positions that exist (causal: half the square), weights read once a
step in the type they are served in, keys and values of the live positions
only. What the program does beyond that (padding to a bucket, reading
``max_len`` cache rows, recomputing) is not counted, so a share of a peak
computed from these cannot pass 100 % by over-counting.
"""

from __future__ import annotations

from typing import Iterable

N_FRAMES = 5


def head_dim(hf: dict) -> int:
    return hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]


def _vision(hf: dict) -> dict:
    vc = hf.get("vision_config", {})
    return {"d": vc.get("hidden_size", 1024), "i": vc.get("intermediate_size", 4096),
            "layers": vc.get("num_layers", 24), "image": vc.get("image_size", 336),
            "patch": vc.get("patch_size", 14), "heads": vc.get("num_heads", 16)}


def tower_tokens(hf: dict) -> int:
    v = _vision(hf)
    return (v["image"] // v["patch"]) ** 2 + 1


def event_tokens(hf: dict) -> int:
    return N_FRAMES + tower_tokens(hf)


def decoder_layer_params(hf: dict) -> int:
    d, hd = hf["hidden_size"], head_dim(hf)
    h, kv, f = (hf["num_attention_heads"], hf["num_key_value_heads"],
                hf["intermediate_size"])
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def decoder_params(hf: dict) -> int:
    """Matmul weights of the decoder stack (no embedding, no head)."""
    return hf["num_hidden_layers"] * decoder_layer_params(hf)


def lm_head_params(hf: dict) -> int:
    return hf["hidden_size"] * hf["vocab_size"]


def tower_params(hf: dict) -> int:
    v = _vision(hf)
    return (v["layers"] * (4 * v["d"] ** 2 + 2 * v["d"] * v["i"])
            + 3 * v["patch"] ** 2 * v["d"])


def projector_params(hf: dict) -> int:
    v, d = _vision(hf), hf["hidden_size"]
    depth = hf.get("mm_projector_depth", 2)
    n = v["d"] * d + (depth - 1) * d * d
    return n + (d * d if "event_feature_adaptor" in hf else 0)


def encode_flops(hf: dict) -> float:
    """Tower + projector + adaptor for one request's five frames."""
    v, s = _vision(hf), tower_tokens(hf)
    per_frame = (2 * s * (tower_params(hf) - 3 * v["patch"] ** 2 * v["d"])
                 + 2 * (s - 1) * 3 * v["patch"] ** 2 * v["d"]
                 + v["layers"] * 4 * s * s * v["d"]
                 + 2 * s * projector_params(hf))
    return float(N_FRAMES * per_frame)


def attention_flops(hf: dict, new: int, before: int = 0) -> float:
    """Causal attention of ``new`` positions that follow ``before`` cached
    ones, all layers: QK^T and PV, 2 FLOP a multiply-add."""
    h, hd, layers = hf["num_attention_heads"], head_dim(hf), hf["num_hidden_layers"]
    pairs = new * before + new * (new + 1) / 2
    return float(layers * 4 * h * hd * pairs)


def prefill_flops(hf: dict, new: int, before: int = 0) -> float:
    """Decoder over ``new`` prompt positions (``before`` come from a cached
    prefix), and the head at the last one."""
    return (2.0 * decoder_params(hf) * new + attention_flops(hf, new, before)
            + 2.0 * lm_head_params(hf))


def decode_flops(hf: dict, context: int) -> float:
    """One token of one row whose cache holds ``context`` positions."""
    return (2.0 * (decoder_params(hf) + lm_head_params(hf))
            + attention_flops(hf, 1, context))


def kv_bytes_per_position(hf: dict, kv_bytes: int = 2) -> int:
    return (2 * hf["num_key_value_heads"] * head_dim(hf)
            * hf["num_hidden_layers"] * kv_bytes)


def weight_bytes_per_step(hf: dict, weight_bytes: int = 1) -> float:
    """What one decode step has to stream: every decoder matrix and the head
    once, in the served type (int8: 1 byte, plus a float32 scale a column)."""
    d, hd = hf["hidden_size"], head_dim(hf)
    h, kv, f = (hf["num_attention_heads"], hf["num_key_value_heads"],
                hf["intermediate_size"])
    cols = hf["num_hidden_layers"] * (h * hd + 2 * kv * hd + d + 2 * f + d) \
        + hf["vocab_size"]
    scales = 4 * cols if weight_bytes == 1 else 0
    return float((decoder_params(hf) + lm_head_params(hf)) * weight_bytes + scales)


def decode_step_bytes(hf: dict, contexts: Iterable[int]) -> float:
    """Bytes one decode step over rows with these live contexts must read."""
    return weight_bytes_per_step(hf) + kv_bytes_per_position(hf) * float(sum(contexts))


def flash_call(q_len: int, kv_len: int, heads: int, hd: int, batch: int = 1,
               causal: bool = True, elem_bytes: int = 2) -> dict:
    """One flash-attention call by its shapes (K and V arrive repeated to
    ``heads``, as the program calls it): FLOP, bytes, and the bound."""
    pairs = q_len * kv_len / (2.0 if causal and q_len == kv_len else 1.0)
    flop = 4.0 * batch * heads * hd * pairs
    byts = float(elem_bytes * batch * heads * hd * (2 * q_len + 2 * kv_len))
    return {"flop": flop, "bytes": byts}


def roofline_s(flop: float, byts: float, peaks: dict) -> tuple:
    """The least time the chip could take, and which bound sets it."""
    t_c, t_m = flop / peaks["bf16_flops_per_s"], byts / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
