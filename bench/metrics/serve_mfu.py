"""Per cent of the chip's bfloat16 peak that the whole served model step
reached over the traced window: the model FLOPs of every prefill and
decode token the window processed (the configuration's counts module, its
``counts`` key: published shapes) over the window's length times the
peak."""

from harness import spec


def read(run):
    f, tr = run.facts, run.trace
    if tr is None or not f.get("prefill_calls") or not run.peaks:
        return None
    cfg, b = f["model"], f["batch"]
    counts = spec.load_module(cfg["counts"])
    ctx = counts.job_contexts(f["prompt_len"], f["gen_tokens"])
    per_step = sum(counts.decode_flops(cfg, b, c) for c in ctx) / len(ctx)
    flops = (f["prefill_calls"] * counts.prefill_flops(cfg, b, f["prompt_len"])
             + f["decode_calls"] * per_step)
    start, end = tr.window()
    return 100.0 * flops / ((end - start) * run.peaks["bf16_flops_per_s"])
