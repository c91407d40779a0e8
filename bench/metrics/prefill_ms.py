"""Device milliseconds per prefill call: the program that ran once per
prefill call the benchmark counted in the traced window (the costliest one
if several did), its executions averaged."""

from harness import tracing


def read(run):
    tr, n = run.trace, run.facts.get("prefill_calls")
    if tr is None or not n:
        return None
    decode, _ = tracing.program_by_calls(tr, run.facts["decode_calls"])
    _, durs = tracing.program_by_calls(tr, n, exclude=(decode,))
    return 1e3 * sum(durs) / len(durs) if durs else None
