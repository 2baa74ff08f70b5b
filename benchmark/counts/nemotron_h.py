"""Counts of the hybrid decoder (``nemotron_h``): ``num_hidden_layers`` blocks
of three kinds, chosen by ``hybrid_override_pattern``: ``M`` a Mamba-2 mixer,
``E`` sparse experts in a latent beside one shared expert, ``*`` GQA
attention. Two kinds of state: keys and values that grow with the position
(the ``*`` layers), and a row's fixed state (the ``M`` layers' ``h`` in
float32 and conv tail). The contract is ``benchmark/flops.py``'s: what the
mathematics needs, never what the program does beyond it.

What an expert layer reads and computes follows the routing, which only the
program's counters know: the ``sched.dispatch`` spans of the ``obs/trace``
ring carry, by decode step and by layer, the held experts that received a
token (``experts_touched``), the assignments that fell on held experts
(``held_assignments``) and the tokens routed (``routed_tokens``). A count
that is given the run reads them; given ``None`` it counts the least the
mathematics could need: no held expert chosen (every assignment of a token
may fall on experts that other chips hold). ``prefill_flops`` is never given
the run (the contract's signature). A prompt is hundreds of tokens of
``num_experts_per_tok`` assignments each, so it counts what the router sends
to the held experts in the mean: ``num_experts_per_tok`` x held / published
assignments a token a layer (5.5 of 22 as served; the decode counter reads
that share, ``held_assignments_pct`` 25.1 of 100, PERF.md section 5).
"""

from __future__ import annotations

from benchmark import flops, loader


def _z(hf: dict) -> dict:
    pattern = str(hf["hybrid_override_pattern"])[:int(hf["num_hidden_layers"])]
    heads, p = int(hf["mamba_num_heads"]), int(hf["mamba_head_dim"])
    g, n = int(hf["n_groups"]), int(hf["ssm_state_size"])
    return dict(
        pattern=pattern, d=int(hf["hidden_size"]), m_heads=heads, p=p, g=g,
        n=n, inner=heads * p, channels=heads * p + 2 * g * n,
        taps=int(hf["conv_kernel"]),
        h=int(hf["num_attention_heads"]), kv=int(hf["num_key_value_heads"]),
        hd=int(hf["head_dim"]),
        width=int(hf.get("published", {}).get("n_routed_experts",
                                              hf["n_routed_experts"])),
        held=int(hf["n_routed_experts"]), top_k=int(hf["num_experts_per_tok"]),
        lat=int(hf["moe_latent_size"]), f=int(hf["moe_intermediate_size"]),
        fs=int(hf["moe_shared_expert_intermediate_size"]))


def layers_of(hf: dict, kind: str) -> int:
    return _z(hf)["pattern"].count(kind)


# -- parameters of one layer, by kind (matrices only) ----------------------------

def mamba_params(hf: dict) -> int:
    z = _z(hf)
    return (z["d"] * (z["inner"] + z["channels"] + z["m_heads"])
            + z["channels"] * z["taps"] + z["inner"] * z["d"])


def attention_params(hf: dict) -> int:
    z = _z(hf)
    return 2 * z["d"] * z["h"] * z["hd"] + 2 * z["d"] * z["kv"] * z["hd"]


def expert_params(hf: dict) -> int:
    """One routed expert: up and down, in the latent."""
    z = _z(hf)
    return 2 * z["lat"] * z["f"]


def moe_dense_params(hf: dict) -> int:
    """What every token of an expert layer uses: the router, the latent's two
    projections and the shared expert."""
    z = _z(hf)
    return (z["d"] * z["width"] + 2 * z["d"] * z["lat"] + 2 * z["d"] * z["fs"])


def dense_params(hf: dict) -> int:
    """Every decoder matrix but the routed experts (no embedding, no head)."""
    return (layers_of(hf, "M") * mamba_params(hf)
            + layers_of(hf, "*") * attention_params(hf)
            + layers_of(hf, "E") * moe_dense_params(hf))


def recurrence_flops(hf: dict) -> float:
    """One position of one ``M`` layer's recurrence: ``h <- a h + (dt x) (x)
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
            + layers_of(hf, "M") * recurrence_flops(hf)
            + layers_of(hf, "E") * held_assignments * 2.0 * expert_params(hf))


def attention_flops(hf: dict, new: int, before: int = 0) -> float:
    """Causal attention of ``new`` positions that follow ``before`` cached
    ones, the ``*`` layers: QK^T and PV, 2 FLOP a multiply-add."""
    z = _z(hf)
    pairs = new * before + new * (new + 1) / 2
    return float(layers_of(hf, "*") * 4 * z["h"] * z["hd"] * pairs)


def prefill_flops(hf: dict, new: int, before: int = 0) -> float:
    """Decoder over ``new`` prompt positions and the head at the last one;
    the routed experts at the held share of a token's assignments (the
    module's text)."""
    z = _z(hf)
    held = z["top_k"] * z["held"] / z["width"]
    return (_token_flops(hf, held) * new + attention_flops(hf, new, before)
            + 2.0 * flops.lm_head_params(hf))


def held_assignments_per_token(run) -> float:
    """Assignments that fell on held experts, a token a layer, over the
    decode steps the run's ring recorded in its window; 0 without a run."""
    held = tokens = layers = 0
    for step in decode_steps(run):
        held += sum(step["held_assignments"])
        tokens += step["routed_tokens"]
        layers = len(step["held_assignments"])
    return held / (tokens * layers) if tokens else 0.0


def decode_flops(hf: dict, context: int, run=None) -> float:
    """One token of one row whose cache holds ``context`` positions; the
    routed experts as the run's counter says a token met them."""
    return (_token_flops(hf, held_assignments_per_token(run))
            + 2.0 * flops.lm_head_params(hf) + attention_flops(hf, 1, context))


def state_bytes_per_position(hf: dict) -> int:
    """Keys and values of one position, the ``*`` layers."""
    z = _z(hf)
    return (2 * z["kv"] * z["hd"] * layers_of(hf, "*")
            * loader.served_bytes(hf)["state"])


def state_bytes_per_row(hf: dict) -> int:
    """What one token of a row reads and writes whatever its position: each
    ``M`` layer's ``h`` (float32: 4 B whatever the served type) and conv
    tail (``conv_kernel - 1`` inputs in the served type), read and written."""
    z = _z(hf)
    layer = (z["m_heads"] * z["p"] * z["n"] * 4
             + (z["taps"] - 1) * z["channels"] * loader.served_bytes(hf)["state"])
    return 2 * layers_of(hf, "M") * layer


def segments(run, lo: float, hi: float):
    """The counters of each decode segment whose ``sched.dispatch`` span began
    in [lo, hi) (seconds on the run's clock) and ran a step: the span's args
    (``experts_touched``, ``expert_fullest``, ``held_assignments`` by step and
    layer; ``routed_tokens`` by step). Nothing where the program has no such
    counter."""
    for e in run.ring:
        args = e.get("args") or {}
        if (e.get("name") == "dispatch" and e.get("ph") == "X"
                and lo * 1e6 <= e.get("ts", 0) < hi * 1e6
                and args.get("routed_tokens")):
            yield args


def decode_steps(run):
    """The decode steps the run recorded, one dict a step, of the traced
    window where there is one, else of the run's; none without a run."""
    if run is None:
        return
    if run.trace is not None and "t0" in run.trace:
        lo, hi = run.trace["t0"], run.trace["t1"]
    else:
        lo, hi = run.t0, run.t1
    for args in segments(run, lo, hi):
        for i, tokens in enumerate(args["routed_tokens"]):
            yield {"experts_touched": args["experts_touched"][i],
                   "expert_fullest": args["expert_fullest"][i],
                   "held_assignments": args["held_assignments"][i],
                   "routed_tokens": tokens}


def experts_read_per_step(run) -> float:
    """Distinct held experts a decode step's tokens chose, all layers, mean
    over the recorded steps; 0 without a run or a counter."""
    steps = [sum(s["experts_touched"]) for s in decode_steps(run)]
    return sum(steps) / len(steps) if steps else 0.0


def weight_bytes_per_step(hf: dict, run=None) -> float:
    """What one decode step has to stream, in the served type: every matrix
    that every token uses and the head, once; and of the routed experts the
    distinct held ones the step's tokens chose (11.0 MB each at the served
    sizes), as the program's counter says. Without a run: none of them."""
    weight = loader.served_bytes(hf)["weight"]
    return float((dense_params(hf) + flops.lm_head_params(hf)
                  + experts_read_per_step(run) * expert_params(hf)) * weight)
