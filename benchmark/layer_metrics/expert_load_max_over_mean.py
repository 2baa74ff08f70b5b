"""How unevenly a decode step's tokens fall on the held experts: the tokens
of the fullest held expert over the mean held expert's (the assignments that
fell on held experts / the experts held), a step a layer; the mean over a
segment's steps and layers, then the median over the segments
(``sched.dispatch`` spans, counters ``expert_fullest`` and
``held_assignments``) that began in the window. 1 is even. Nothing where the
spans carry no such counters."""

from statistics import median

from benchmark import loader


def read(run):
    held = int(run.hf.get("n_routed_experts", 0))
    segments = loader.module_at("counts/nemotron_h.py").segments
    ratios = []
    for args in segments(run, run.t0, run.t1):
        cells = [full * held / got
                 for fulls, gots in zip(args["expert_fullest"],
                                        args["held_assignments"])
                 for full, got in zip(fulls, gots) if got]
        if cells:
            ratios.append(sum(cells) / len(cells))
    return median(ratios) if ratios and held else None
