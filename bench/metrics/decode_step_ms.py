"""Device milliseconds per decode call: the program that ran once per
decode call the benchmark counted in the traced window, executions
averaged."""

from harness import tracing


def read(run):
    tr, n = run.trace, run.facts.get("decode_calls")
    if tr is None or not n:
        return None
    _, durs = tracing.program_by_calls(tr, n)
    return 1e3 * sum(durs) / len(durs) if durs else None
