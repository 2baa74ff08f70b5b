"""Device seconds of the decode segment programs (the decode class of
benchmark/programs.json) in the traced window over the answer tokens that
reached their clients in it."""

from benchmark.measure import class_seconds, traced_tokens


def read(run):
    if run.trace is None:
        return None
    tokens, secs = traced_tokens(run), class_seconds(run, "decode")
    return secs * 1e3 / tokens if tokens and secs else None
