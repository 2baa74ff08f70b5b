"""Counts of the Granite-4.0-H decoder (``granitemoehybrid``): each of the first
``num_hidden_layers`` entries of ``layer_types`` is a mixer (``mamba``: a
Mamba-2 mixer; ``attention``: GQA) followed by sparse experts beside one
shared expert. Gated SwiGLU experts on the full width (three matrices of
``hidden_size`` x ``intermediate_size`` each), a gated shared expert of
``shared_intermediate_size``, a router over every expert of the deployment,
and a head tied to the embedding table (read once a step, as the head: the
embedding lookup reads a row a token and is not counted). Two kinds of state:
keys and values by position (the attention layers), and a row's fixed state
(each mamba layer's ``h`` in float32 and conv tail). The contract is
``benchmark/flops.py``'s: what the mathematics needs, never what the program
does beyond it.

What an expert layer reads and computes follows the routing, which only the
program's counters know: the ``sched.dispatch`` spans of the ``obs/trace``
ring carry, by decode step and by layer, the held experts that received a
token (``experts_touched``), the assignments that fell on held experts
(``held_assignments``) and the tokens routed (``routed_tokens``); they are
read as ``counts/nemotron_h.py`` reads them (``decode_steps``). Given
``None`` a count counts the least the mathematics could need: no held expert
chosen. ``prefill_flops`` counts what the router sends to the held experts
in the mean: ``num_experts_per_tok`` x held / published assignments a token a
layer (5 of 10 as served).
"""

from __future__ import annotations

from benchmark import flops, loader


def _z(hf: dict) -> dict:
    types = list(hf["layer_types"])[:int(hf["num_hidden_layers"])]
    heads, p = int(hf["mamba_n_heads"]), int(hf["mamba_d_head"])
    g, n = int(hf["mamba_n_groups"]), int(hf["mamba_d_state"])
    h = int(hf["num_attention_heads"])
    return dict(
        types=types, d=int(hf["hidden_size"]), m_heads=heads, p=p, g=g, n=n,
        inner=heads * p, channels=heads * p + 2 * g * n,
        taps=int(hf["mamba_d_conv"]), h=h, kv=int(hf["num_key_value_heads"]),
        hd=int(hf.get("head_dim") or int(hf["hidden_size"]) // h),
        width=int(hf.get("published", {}).get("num_local_experts",
                                              hf["num_local_experts"])),
        held=int(hf["num_local_experts"]), top_k=int(hf["num_experts_per_tok"]),
        f=int(hf["intermediate_size"]), fs=int(hf["shared_intermediate_size"]))


def layers_of(hf: dict, kind: str) -> int:
    """Layers of one kind: ``mamba``, ``attention``, or ``moe`` (every
    layer's expert sublayer)."""
    types = _z(hf)["types"]
    return len(types) if kind == "moe" else types.count(kind)


# -- parameters of one layer, by kind (matrices only) ----------------------------

def mamba_params(hf: dict) -> int:
    z = _z(hf)
    return (z["d"] * (z["inner"] + z["channels"] + z["m_heads"])
            + z["channels"] * z["taps"] + z["inner"] * z["d"])


def attention_params(hf: dict) -> int:
    z = _z(hf)
    return 2 * z["d"] * z["h"] * z["hd"] + 2 * z["d"] * z["kv"] * z["hd"]


def expert_params(hf: dict) -> int:
    """One routed expert: gate, up and down."""
    z = _z(hf)
    return 3 * z["d"] * z["f"]


def moe_dense_params(hf: dict) -> int:
    """What every token of an expert sublayer uses: the router over every
    expert of the deployment and the shared expert."""
    z = _z(hf)
    return z["d"] * z["width"] + 3 * z["d"] * z["fs"]


def dense_params(hf: dict) -> int:
    """Every decoder matrix but the routed experts (no embedding, no head)."""
    return (layers_of(hf, "mamba") * mamba_params(hf)
            + layers_of(hf, "attention") * attention_params(hf)
            + layers_of(hf, "moe") * moe_dense_params(hf))


def recurrence_flops(hf: dict) -> float:
    """One position of one mamba layer's recurrence: ``h <- a h + (dt x) (x)
    B`` (a multiply, a multiply, an add an element) and ``y = h C`` (a
    multiply-add an element), over heads x head size x state."""
    z = _z(hf)
    return 5.0 * z["m_heads"] * z["p"] * z["n"]


# -- the contract ----------------------------------------------------------------

def _token_flops(hf: dict, held_assignments: float) -> float:
    """One position through every layer, attention over positions apart:
    2 FLOP a multiply-add of every matrix it meets, the recurrence, and the
    routed experts of ``held_assignments`` assignments a layer."""
    return (2.0 * dense_params(hf)
            + layers_of(hf, "mamba") * recurrence_flops(hf)
            + layers_of(hf, "moe") * held_assignments * 2.0 * expert_params(hf))


def attention_flops(hf: dict, new: int, before: int = 0) -> float:
    """Causal attention of ``new`` positions that follow ``before`` cached
    ones, the attention layers: QK^T and PV, 2 FLOP a multiply-add."""
    z = _z(hf)
    pairs = new * before + new * (new + 1) / 2
    return float(layers_of(hf, "attention") * 4 * z["h"] * z["hd"] * pairs)


def prefill_flops(hf: dict, new: int, before: int = 0) -> float:
    """Decoder over ``new`` prompt positions and the head at the last one;
    the routed experts at the held share of a token's assignments (the
    module's text)."""
    z = _z(hf)
    held = z["top_k"] * z["held"] / z["width"]
    return (_token_flops(hf, held) * new + attention_flops(hf, new, before)
            + 2.0 * flops.lm_head_params(hf))


def decode_steps(run):
    """The decode steps the run recorded, one dict a step (the counters of
    ``counts/nemotron_h.py``'s ``decode_steps``); none without a run."""
    return loader.module_at("counts/nemotron_h.py").decode_steps(run)


def held_assignments_per_token(run) -> float:
    """Assignments that fell on held experts, a token a layer, over the
    decode steps the run's ring recorded in its window; 0 without a run."""
    held = tokens = layers = 0
    for step in decode_steps(run):
        held += sum(step["held_assignments"])
        tokens += step["routed_tokens"]
        layers = len(step["held_assignments"])
    return held / (tokens * layers) if tokens else 0.0


def experts_read_per_step(run) -> float:
    """Distinct held experts a decode step's tokens chose, all layers, mean
    over the recorded steps; 0 without a run or a counter."""
    steps = [sum(s["experts_touched"]) for s in decode_steps(run)]
    return sum(steps) / len(steps) if steps else 0.0


def decode_flops(hf: dict, context: int, run=None) -> float:
    """One token of one row whose cache holds ``context`` positions; the
    routed experts as the run's counter says a token met them."""
    return (_token_flops(hf, held_assignments_per_token(run))
            + 2.0 * flops.lm_head_params(hf) + attention_flops(hf, 1, context))


def state_bytes_per_position(hf: dict) -> int:
    """Keys and values of one position, the attention layers."""
    z = _z(hf)
    return (2 * z["kv"] * z["hd"] * layers_of(hf, "attention")
            * loader.served_bytes(hf)["state"])


def state_bytes_per_row(hf: dict) -> int:
    """What one token of a row reads and writes whatever its position: each
    mamba layer's ``h`` (float32: 4 B whatever the served type) and conv
    tail (``mamba_d_conv - 1`` inputs in the served type), read and written."""
    z = _z(hf)
    layer = (z["m_heads"] * z["p"] * z["n"] * 4
             + (z["taps"] - 1) * z["channels"] * loader.served_bytes(hf)["state"])
    return 2 * layers_of(hf, "mamba") * layer


def weight_bytes_per_step(hf: dict, run=None) -> float:
    """What one decode step has to stream, in the served type: every matrix
    that every token uses and the tied head (the embedding table, once);
    and of the routed experts the distinct held ones the step's tokens chose
    (18.9 MB each at the served sizes), as the program's counter says.
    Without a run: none of them."""
    weight = loader.served_bytes(hf)["weight"]
    return float((dense_params(hf) + flops.lm_head_params(hf)
                  + experts_read_per_step(run) * expert_params(hf)) * weight)
