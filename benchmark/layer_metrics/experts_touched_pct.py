"""Held experts a decode step read: the distinct held experts that received a
token, a layer, over the experts held (``n_routed_experts`` of the
configuration's file), in percent; the mean over a segment's steps and
layers, then the median over the segments (``sched.dispatch`` spans of the
``obs/trace`` ring, counter ``experts_touched``) that began in the window.
Nothing where the spans carry no such counter."""

from statistics import median

from benchmark import loader


def read(run):
    held = int(run.hf.get("n_routed_experts", 0))
    segments = loader.module_at("counts/nemotron_h.py").segments
    shares = []
    for args in segments(run, run.t0, run.t1):
        cells = [n for step in args["experts_touched"] for n in step]
        shares.append(100.0 * sum(cells) / (len(cells) * held))
    return median(shares) if shares and held else None
