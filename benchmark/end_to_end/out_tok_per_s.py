"""Answer tokens that reached their clients inside the window, whatever
request they belong to, over the whole window. Counted by the client from the
streamed deltas (a character a token), on the harness's clock."""


def read(run):
    n = run.tokens_in(run.t0, run.t1)
    return n / run.seconds if n else None
