"""The share of the decode steps' assignments (``num_experts_per_tok`` a
token a layer) that fell on experts held here, in percent, over the
``sched.dispatch`` spans (counters ``held_assignments``, ``routed_tokens``)
that began in the window: held / deployment's experts where the router is
even. Nothing where the spans carry no such counters."""

from benchmark import loader


def read(run):
    top_k = int(run.hf.get("num_experts_per_tok", 0))
    segments = loader.module_at("counts/nemotron_h.py").segments
    held = offered = 0
    for args in segments(run, run.t0, run.t1):
        for gots, tokens in zip(args["held_assignments"],
                                args["routed_tokens"]):
            held += sum(gots)
            offered += top_k * tokens * len(gots)
    return 100.0 * held / offered if offered else None
