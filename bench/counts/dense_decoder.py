"""Operations and bytes of a dense decoder (qwen2 family), from the
configuration's published shapes (hf ``config.json`` keys).

A configuration names its counts module under its ``counts`` key; the
per-layer readers (``metrics/prefill_roofline.py``,
``metrics/decode_roofline.py``, ``metrics/serve_mfu.py``) load it from
there.  Every counts module offers these five functions, with these
signatures (``cfg`` is the configuration's dict):

* ``prefill_flops(cfg, batch, prompt_len)`` and
  ``prefill_bytes(cfg, batch, prompt_len)``: one prefill call of
  ``batch`` rows of ``prompt_len`` tokens;
* ``decode_flops(cfg, batch, context)`` and
  ``decode_bytes(cfg, batch, context)``: one decode step of ``batch``
  rows, each attending over ``context`` positions;
* ``job_contexts(prompt_len, gen_tokens)``: the context of each decode
  step of a job.

This module counts:

* parameters: per layer q/k/v/o projections (q/k/v biases where
  ``attention_bias`` says so), the gated MLP and two RMSNorm scales; the embedding (tied output head) and the
  final norm;
* FLOPs per token: 2 per matmul weight (the tied head counted as a
  matmul wherever logits are produced), plus attention, 2 x 2 x layers x
  context x (heads x head size) for the scores and the weighted values;
  prefill produces logits for the last position of each row only, as
  serving needs;
* bytes per decode step: every weight once in bfloat16, plus the keys and
  values of the context read in bfloat16 (the step's new key and value
  written are left out, a batch x layers x 2 x kv width row);
* bytes per prefill: every weight once in bfloat16, plus the keys and
  values of the prompt written in bfloat16 (activations left out).
"""

BYTES = 2  # bfloat16


def _dims(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = d // h
    return (d, cfg["intermediate_size"], cfg["num_hidden_layers"], h,
            cfg["num_key_value_heads"], hd, cfg["vocab_size"])


def matmul_weights_per_layer(cfg) -> int:
    d, f, _, h, kv, hd, _ = _dims(cfg)
    return 2 * d * h * hd + 2 * d * kv * hd + 3 * d * f


def parameters(cfg) -> int:
    d, f, L, h, kv, hd, V = _dims(cfg)
    bias = (h + 2 * kv) * hd if cfg["attention_bias"] else 0
    per_layer = matmul_weights_per_layer(cfg) + bias + 2 * d
    return L * per_layer + V * d + d


def flops_per_token(cfg, context: int, logits: bool) -> float:
    d, _, L, h, _, hd, V = _dims(cfg)
    out = 2.0 * L * matmul_weights_per_layer(cfg)
    out += 4.0 * L * context * h * hd
    if logits:
        out += 2.0 * d * V
    return out


def prefill_flops(cfg, batch: int, prompt_len: int) -> float:
    """A prefill of ``batch`` rows of ``prompt_len`` tokens (causal)."""
    d, _, L, h, _, hd, V = _dims(cfg)
    base = 2.0 * L * matmul_weights_per_layer(cfg) * prompt_len
    attn = 4.0 * L * h * hd * prompt_len * (prompt_len + 1) / 2
    return batch * (base + attn + 2.0 * d * V)


def decode_flops(cfg, batch: int, context: int) -> float:
    return batch * flops_per_token(cfg, context, logits=True)


def decode_bytes(cfg, batch: int, context: int) -> float:
    _, _, L, _, kv, hd, _ = _dims(cfg)
    return BYTES * (parameters(cfg) + 2 * L * batch * context * kv * hd)


def prefill_bytes(cfg, batch: int, prompt_len: int) -> float:
    _, _, L, _, kv, hd, _ = _dims(cfg)
    return BYTES * (parameters(cfg) + 2 * L * batch * prompt_len * kv * hd)


def job_contexts(prompt_len: int, gen_tokens: int):
    """Context read by each decode step of a job: step i writes position
    prompt_len + i and attends over prompt_len + i + 1 positions."""
    return [prompt_len + i + 1 for i in range(gen_tokens - 1)]
