"""Per cent of the traced window in which no program ran on the device,
in a cell that serves (``bench.serve`` spans)."""

from harness import tracing


def read(run):
    return tracing.idle_share(run.trace, "bench.serve")
