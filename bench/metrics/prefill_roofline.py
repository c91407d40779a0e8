"""Per cent of its roofline a prefill call reached: the least time a
prefill of the cell's batch and prompt can take, the larger of its model
FLOPs (the configuration's counts module, its ``counts`` key: published
shapes, causal attention) over the chip's bfloat16 peak and its bytes
(every bfloat16 weight read once, and the keys and values of the prompt
written) over HBM bandwidth, divided by the measured device time per
prefill call.  Compute binds: a prefill of 8 x 1,024 tokens does about
8,000 operations per weight byte, against the chip's 240."""

from harness import spec, tracing


def read(run):
    tr, n = run.trace, run.facts.get("prefill_calls")
    if tr is None or not n or not run.peaks:
        return None
    decode, _ = tracing.program_by_calls(tr, run.facts["decode_calls"])
    _, durs = tracing.program_by_calls(tr, n, exclude=(decode,))
    if not durs:
        return None
    f, peaks = run.facts, run.peaks
    counts = spec.load_module(f["model"]["counts"])
    flops = counts.prefill_flops(f["model"], f["batch"], f["prompt_len"])
    nbytes = counts.prefill_bytes(f["model"], f["batch"], f["prompt_len"])
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (sum(durs) / len(durs))
