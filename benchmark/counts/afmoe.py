"""Counts of the ``afmoe`` decoder (Arcee Trinity): ``num_hidden_layers``
layers of gated GQA attention, each a window layer (``sliding_attention``:
rotary, ``sliding_window`` keys a query at most) or a global layer
(``full_attention``), over a dense SwiGLU MLP in the first
``num_dense_layers`` and sigmoid-routed SwiGLU experts beside one shared
expert in the others. Two kinds of keys and values: a global layer's grow
with the position, a window layer's are a ring of ``sliding_window`` slots a
row. The contract is ``benchmark/flops.py``'s: what the mathematics needs,
never what the program does beyond it.

**Attention counts the band**: a query at position ``p`` of a window layer
sees ``min(p + 1, sliding_window)`` keys, of a global layer ``p + 1``.

**State.** ``state_bytes_per_position`` is the global layers' keys and
values of one position; ``state_bytes_per_row`` is the window layers' rings,
``sliding_window`` slots each, which a decode step reads whole whatever the
row's position. That is exact while every context is at or past the window
(a shorter row's step needs only its ``length`` slots and the count would
overstate what it must read): the one cell of this configuration sends
prompts of three windows, so every row is past the window from its first
step, and ``rows_past_window_pct`` shows it (100).

What an expert layer reads and computes follows the routing, which only the
program's counters know; they are the hybrid's (``sched.dispatch`` spans:
``experts_touched``, ``held_assignments``, ``routed_tokens``), read by
``counts/nemotron_h.py``'s ``segments`` / ``decode_steps``. A count that
gets no run counts the least the mathematics could need (no held expert
chosen); ``prefill_flops`` counts the mean share of a token's assignments
that the router sends to held experts (``num_experts_per_tok`` x held /
published: 0.5 of 4 as served).
"""

from __future__ import annotations

from benchmark import flops, loader


def _z(hf: dict) -> dict:
    depth = int(hf["num_hidden_layers"])
    kept = hf.get("layers_kept", range(depth))
    types = [hf["layer_types"][int(i)] for i in kept]
    held = int(hf["num_experts"])
    return dict(
        types=types, dense_layers=int(hf["num_dense_layers"]),
        window=int(hf["sliding_window"]), d=int(hf["hidden_size"]),
        i=int(hf["intermediate_size"]), f=int(hf["moe_intermediate_size"]),
        h=int(hf["num_attention_heads"]), kv=int(hf["num_key_value_heads"]),
        hd=int(hf["head_dim"]), held=held,
        width=int(hf.get("published", {}).get("num_experts", held)),
        top_k=int(hf["num_experts_per_tok"]))


def _hybrid():
    """The readers of the expert counters, which both sparse decoders write
    under the same names and layout."""
    return loader.module_at("counts/nemotron_h.py",
                            ("decode_steps", "held_assignments_per_token",
                             "experts_read_per_step"))


def layers_of(hf: dict, kind: str) -> int:
    return _z(hf)["types"].count(kind)


def expert_layers(hf: dict) -> int:
    z = _z(hf)
    return len(z["types"]) - z["dense_layers"]


# -- parameters (matrices only) ---------------------------------------------------

def attention_params(hf: dict) -> int:
    """q, gate and o at the heads' width, k and v at the kv heads'."""
    z = _z(hf)
    return 3 * z["d"] * z["h"] * z["hd"] + 2 * z["d"] * z["kv"] * z["hd"]


def expert_params(hf: dict) -> int:
    """One expert, routed or shared: gate, up and down."""
    z = _z(hf)
    return 3 * z["d"] * z["f"]


def dense_params(hf: dict) -> int:
    """Every decoder matrix that every token uses (no embedding, no head):
    attention, the dense layers' MLP, each expert layer's router and shared
    expert."""
    z = _z(hf)
    return (len(z["types"]) * attention_params(hf)
            + z["dense_layers"] * 3 * z["d"] * z["i"]
            + expert_layers(hf) * (z["d"] * z["width"] + expert_params(hf)))


# -- attention over positions -----------------------------------------------------

def band_pairs(new: int, before: int, window: int) -> float:
    """Query-key pairs of ``new`` positions after ``before`` where a query at
    position ``p`` sees ``min(p + 1, window)`` keys."""
    def upto(n):  # over positions 0 .. n-1
        if n <= window:
            return n * (n + 1) / 2
        return window * (window + 1) / 2 + (n - window) * window
    return float(upto(before + new) - upto(before))


def causal_pairs(new: int, before: int) -> float:
    return float(new * before + new * (new + 1) / 2)


def attention_flops(hf: dict, new: int, before: int = 0) -> float:
    """QK^T and PV over the pairs each kind of layer has, 2 FLOP a
    multiply-add."""
    z = _z(hf)
    pairs = (layers_of(hf, "full_attention") * causal_pairs(new, before)
             + layers_of(hf, "sliding_attention")
             * band_pairs(new, before, z["window"]))
    return float(4 * z["h"] * z["hd"] * pairs)


def band_flash_call(rows_heads: int, positions: int, head_size: int,
                    hf: dict, elem_bytes: int = 2) -> dict:
    """One banded flash call by its output's shape (rows x heads, positions,
    head size): FLOP over the band's pairs; bytes of q and the output at the
    heads' count and of K and V at the kv heads' (the kernel reads them
    unrepeated)."""
    z = _z(hf)
    flop = 4.0 * rows_heads * head_size * band_pairs(positions, 0, z["window"])
    per = elem_bytes * head_size * positions
    byts = float(2 * per * rows_heads + 2 * per * rows_heads * z["kv"] / z["h"])
    return {"flop": flop, "bytes": byts}


# -- the contract ----------------------------------------------------------------

def _token_flops(hf: dict, held_assignments: float) -> float:
    """One position through every layer, attention over positions apart."""
    return (2.0 * dense_params(hf)
            + expert_layers(hf) * held_assignments * 2.0 * expert_params(hf))


def prefill_flops(hf: dict, new: int, before: int = 0) -> float:
    z = _z(hf)
    held = z["top_k"] * z["held"] / z["width"]
    return (_token_flops(hf, held) * new + attention_flops(hf, new, before)
            + 2.0 * flops.lm_head_params(hf))


def decode_flops(hf: dict, context: int, run=None) -> float:
    """One token of one row whose state holds ``context`` positions; the
    routed experts as the run's counter says a token met them."""
    return (_token_flops(hf, _hybrid().held_assignments_per_token(run))
            + 2.0 * flops.lm_head_params(hf) + attention_flops(hf, 1, context))


def state_bytes_per_position(hf: dict) -> int:
    """Keys and values of one position, the global layers."""
    z = _z(hf)
    return (2 * z["kv"] * z["hd"] * layers_of(hf, "full_attention")
            * loader.served_bytes(hf)["state"])


def state_bytes_per_row(hf: dict) -> int:
    """The window layers' rings, read whole by a step: ``sliding_window``
    slots of keys and values a layer. Exact while the row's context is at or
    past the window (the module's text)."""
    z = _z(hf)
    return (2 * z["kv"] * z["hd"] * z["window"]
            * layers_of(hf, "sliding_attention")
            * loader.served_bytes(hf)["state"])


def weight_bytes_per_step(hf: dict, run=None) -> float:
    """What one decode step has to stream, in the served type: every matrix
    that every token uses and the head, once; and of the routed experts the
    distinct held ones the step's tokens chose (56.6 MB each at the served
    sizes), as the program's counter says. Without a run: none of them."""
    weight = loader.served_bytes(hf)["weight"]
    return float((dense_params(hf) + flops.lm_head_params(hf)
                  + _hybrid().experts_read_per_step(run) * expert_params(hf))
                 * weight)
