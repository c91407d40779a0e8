"""Per cent of its roofline a decode step reached: the bytes a step must
read (every bfloat16 weight once, and the keys and values of its context,
by the configuration's counts module, its ``counts`` key, averaged over
the steps of a job) over HBM bandwidth, divided by the measured device
time per decode call.  Memory binds: a decode step of batch 8 does about
8 operations per weight byte read, against the chip's 240 operations per
byte."""

from harness import spec, tracing


def read(run):
    tr, n = run.trace, run.facts.get("decode_calls")
    if tr is None or not n or not run.peaks:
        return None
    _, durs = tracing.program_by_calls(tr, n)
    if not durs:
        return None
    f = run.facts
    counts = spec.load_module(f["model"]["counts"])
    ctx = counts.job_contexts(f["prompt_len"], f["gen_tokens"])
    nbytes = sum(counts.decode_bytes(f["model"], f["batch"], c)
                 for c in ctx) / len(ctx)
    least = nbytes / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (sum(durs) / len(durs))
