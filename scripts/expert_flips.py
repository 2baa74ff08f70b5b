"""Where the served decoder and the float32 reference choose different experts,
and what that does to the gap that ``benchmark/correct.py`` reads.

    python3 scripts/expert_flips.py <configuration> <seed> <positions> <dense|flash> <out.json>

One seeded tree as the benchmark makes it, one seeded sequence of token ids,
teacher-forced through (a) the program's ``nemotron_h.forward`` in bfloat16,
(b) the reference in float32, (c) the reference with ``lower="int8"`` (the
control). At each position: the token (a) and (c) put first, its gap in (b)'s
logits, and in how many expert layers the chosen experts differ from (b)'s.
Not part of the benchmark: it shows why the widest gap of a decoder with
sparse experts does not tell the control from the served model (PERF.md
section 6, PR 28). ``flash`` on the chip; ``dense`` under JAX_PLATFORMS=cpu
(the published widths take ~7 minutes and 40 GB there).
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:] = [ROOT] + [p for p in sys.path if p != ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark import loader, weights  # noqa: E402
from benchmark.reference import _f32, _rms_norm  # noqa: E402
from benchmark.references import nemotron_h as ref  # noqa: E402
from eventgpt_tpu.config import from_hf_config  # noqa: E402
from eventgpt_tpu.models import experts as experts_mod, nemotron_h as nh  # noqa: E402
from eventgpt_tpu.models.synthetic import served_shapes  # noqa: E402


def main(config, seed, t, attn, out_path):
    hf = loader.read_json(loader.config_file(config))
    cfg = from_hf_config(hf, attn_impl=attn)
    lc = cfg.llama
    tree = weights.make_tree(
        served_shapes(cfg, jnp.bfloat16, "none", False), seed,
        never=(loader.id_tokenizer().eos_token_id,),
        rules=loader.named(hf, "weights"))
    dec = tree["llama"]
    ids = np.random.default_rng([seed, 28]).integers(0, lc.vocab_size, t)
    embeds = dec["embed_tokens"][jnp.asarray(ids)]
    z = ref._sizes(hf)
    pattern = str(hf["hybrid_override_pattern"])[:int(hf["num_hidden_layers"])]
    k = z["top_k"]

    captured = []
    route = experts_mod.route

    def spy(routing, router, bias, y):
        experts, w = route(routing, router, bias, y)
        captured.append(experts)
        return experts, w

    @jax.jit
    def program(dec, x):
        captured.clear()
        experts_mod.route = spy
        try:
            logits = nh.forward(dec, lc, x[None])[0]
        finally:
            experts_mod.route = route
        return logits.astype(jnp.float32).argmax(-1), jnp.stack(captured)

    def chosen_by(layer, x, lower):
        w_of = ref._weights(lower)
        with jax.default_matmul_precision("highest"):
            y = _rms_norm(x, layer["norm"], z["eps"])
            s = jax.nn.sigmoid(y @ w_of(layer["router"]))
            top, chosen = jax.lax.top_k(
                s + _f32(layer["e_score_correction_bias"]), k + 1)
        return chosen[:, :k], top[:, k - 1] - top[:, k]

    chosen_by = jax.jit(chosen_by, static_argnames=("lower",))

    def reference(lower):
        x = embeds.astype(jnp.float32)
        chosen, margin = [], []
        for kind, layer in zip(pattern, dec["layers"]):
            if kind == "M":
                x = ref._mamba(layer, x, m_heads=z["m_heads"],
                               m_head_dim=z["m_head_dim"], groups=z["groups"],
                               state=z["state"], taps=z["taps"], eps=z["eps"],
                               lower=lower)
            elif kind == "E":
                c, m = chosen_by(layer, x, lower)
                chosen.append(c)
                margin.append(m)
                x = ref._experts(layer, x, top_k=k, held=z["held"],
                                 offset=z["offset"], scaling=z["scaling"],
                                 norm_topk=z["norm_topk"], eps=z["eps"],
                                 lower=lower)
            else:
                x = ref._attention(layer, x, heads=z["heads"],
                                   kv_heads=z["kv_heads"],
                                   head_dim=z["head_dim"], eps=z["eps"],
                                   lower=lower)
        logits = ref._head({"final_norm": dec["final_norm"],
                            "lm_head": dec["lm_head"]}, x, jnp.arange(t),
                           eps=z["eps"], lower=lower)
        return (np.asarray(logits), np.asarray(jnp.stack(chosen)),
                np.asarray(jnp.stack(margin)))

    served_tok, served_chosen = (np.asarray(v) for v in program(dec, embeds))
    logits, ref_chosen, ref_margin = reference(None)
    low_logits, low_chosen, _ = reference("int8")
    best = logits.max(-1)
    lo, hi = z["offset"], z["offset"] + z["held"]

    def report(name, tok, chosen):
        gap = best - logits[np.arange(t), tok]
        # by layer and position: experts one side chose and the other did
        # not, of all and of those this chip holds
        differ = np.zeros(chosen.shape[:2], int)
        differ_held = np.zeros(chosen.shape[:2], int)
        for layer in range(chosen.shape[0]):
            for i in range(t):
                a, b = set(chosen[layer, i]), set(ref_chosen[layer, i])
                d = a ^ b
                differ[layer, i] = len(d) // 2
                differ_held[layer, i] = sum(lo <= e < hi for e in d)
        same = differ.sum(0) == 0
        same_held = differ_held.sum(0) == 0
        at = int(gap.argmax())
        pct = lambda v, q: float(np.percentile(v, q)) if len(v) else None
        worst_layers = []
        for layer in range(chosen.shape[0]):
            a, b = set(chosen[layer, at]), set(ref_chosen[layer, at])
            worst_layers.append({
                "layer": layer,
                "only_here": sorted(int(e) for e in a - b),
                "only_reference": sorted(int(e) for e in b - a),
                "held_among_them": int(differ_held[layer, at]),
                "reference_margin_22_23": float(ref_margin[layer, at])})
        out = {
            "positions": t,
            "tokens_not_the_references_first": int((gap > 0).sum()),
            "widest_gap": float(gap.max()), "mean_gap": float(gap.mean()),
            "p99_gap": pct(gap, 99),
            "positions_with_every_choice_the_same": int(same.sum()),
            "widest_gap_there": float(gap[same].max()) if same.any() else None,
            "mean_gap_there": float(gap[same].mean()) if same.any() else None,
            "positions_with_the_held_choices_the_same": int(same_held.sum()),
            "widest_gap_held_same": (float(gap[same_held].max())
                                     if same_held.any() else None),
            "widest_gap_where_a_choice_differs": (
                float(gap[~same].max()) if (~same).any() else None),
            "mean_gap_where_a_choice_differs": (
                float(gap[~same].mean()) if (~same).any() else None),
            "experts_that_differ_a_layer_a_position": float(differ.mean()),
            "share_of_layer_positions_that_differ": float((differ > 0).mean()),
            "median_reference_margin_22_23": pct(ref_margin.ravel(), 50),
            "widest_at": at, "layers_at_the_widest": worst_layers,
            "ten_widest": [{"at": int(i), "gap": float(gap[i]),
                            "layers_that_differ": int((differ[:, i] > 0).sum()),
                            "held_that_differ": int(differ_held[:, i].sum())}
                           for i in np.argsort(-gap)[:10]],
        }
        print(f"[flips] {name}: " + json.dumps(
            {k_: v for k_, v in out.items() if k_ != "layers_at_the_widest"}))
        print(f"[flips] {name} at the widest gap: "
              + json.dumps(worst_layers))
        return out

    result = {"config": config, "seed": seed, "device": str(jax.devices()[0]),
              "served": report("served", served_tok, served_chosen),
              "control_int8": report("control", low_logits.argmax(-1),
                                     low_chosen)}
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4],
         sys.argv[5])
