"""Generated tokens of the requests completed in the window over the
window's whole wall time (the window ends at a ``serve()`` call boundary)."""


def read(run):
    tokens = run.counts.get("tokens")
    if tokens is None or not run.window_s > 0:
        return None
    return tokens / run.window_s
