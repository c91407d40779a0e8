"""Operations and bytes of a decoder with multi-head latent attention and
DeepSeekMoE layers (deepseek_v2 family), held as one chip's share of an
expert-parallel deployment, from the configuration's published shapes (hf
``config.json`` keys, and ``expert_parallel``).

The five functions of every counts module (``dense_decoder.py`` names
them).  Notation: d hidden, L layers (layer 0 dense with MLP width F, the
other L - 1 MoE), H heads, r ``kv_lora_rank``, dn/dr/dv the nope, rope
and value head sizes, E the router's published experts, n the experts
held here, k experts per token, f the expert width, S shared experts, V
the vocabulary.

* parameters: per layer the MLA projections (q d x H(dn+dr), kv_a d x
  (r+dr), kv_b r x H(dn+dv), o H dv x d), the latent's RMSNorm and two
  more; layer 0 its MLP; each MoE layer its router d x E, its n held
  experts and its shared experts (3 d S f); the embedding, the untied
  head and the final norm.
* FLOPs: 2 per matmul weight a token goes through, plus attention.  The
  held experts at their expectation under uniform top-k-of-E routing: a
  token goes through k n / E expert-FFNs here (0.75 for 6 of 64 over 8
  held).  Prefill in the expanded form: kv_b on every position, scores
  2 H (dn+dr) and values 2 H dv per (query, key) pair, causal.  Decode
  in the absorbed form: W_UK and W_UV each 2 H dn r and 2 H r dv a token,
  scores 2 H (r+dr) and the latent weighted sum 2 H r per position of the
  context.  Logits: the last position of each row in prefill, every row
  in decode.
* bytes of a decode step: every weight once in bfloat16 (the router in
  float32) but the input embedding, of which a step gathers b rows only
  (left out), and the held experts, of which a step of b rows touches n (1 -
  ((E - k) / E)**b) in expectation (each row picks k of E, so misses a
  given expert with probability (E - k) / E; 4.36 of 8 at b = 8); plus the
  latent cache of the context, L x b x context x (r + dr) in bfloat16 (the
  step's new entry written left out).  A program that skips the untouched
  experts then reads no more than this count, and one that reads all n
  reads more.
* bytes of a prefill: every weight once but the input embedding, all n
  held experts included, and the latent cache of the prompt written.
"""

BYTES = 2  # bfloat16
ROUTER_BYTES = 4  # the router is held in float32


def _dims(cfg):
    ep = cfg["expert_parallel"]
    return {"d": cfg["hidden_size"], "F": cfg["intermediate_size"],
            "L": cfg["num_hidden_layers"], "H": cfg["num_attention_heads"],
            "r": cfg["kv_lora_rank"], "dn": cfg["qk_nope_head_dim"],
            "dr": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
            "E": ep["published_n_routed_experts"],
            "n": cfg["n_routed_experts"], "k": cfg["num_experts_per_tok"],
            "f": cfg["moe_intermediate_size"], "S": cfg["n_shared_experts"],
            "V": cfg["vocab_size"]}


def _attn_weights(m) -> int:
    d, H, r, dn, dr, dv = (m[x] for x in ("d", "H", "r", "dn", "dr", "dv"))
    return d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d


def _expert(m) -> int:
    return 3 * m["d"] * m["f"]


def _parts(cfg) -> dict:
    """Parameter counts by part."""
    m = _dims(cfg)
    d, L = m["d"], m["L"]
    return {"attn": L * (_attn_weights(m) + m["r"] + 2 * d),
            "dense_mlp": 3 * d * m["F"],
            "router": (L - 1) * d * m["E"],
            "experts": (L - 1) * m["n"] * _expert(m),
            "shared": (L - 1) * m["S"] * _expert(m),
            "embed": m["V"] * d,
            "head": m["V"] * d + d}


def parameters(cfg) -> int:
    return sum(_parts(cfg).values())


def _ffn_flops_per_token(m) -> float:
    """Matmul FLOPs of the FFNs a token goes through, over all layers."""
    routed = m["k"] * m["n"] / m["E"]
    moe = 2.0 * (m["d"] * m["E"] + (m["S"] + routed) * _expert(m))
    return 2.0 * 3 * m["d"] * m["F"] + (m["L"] - 1) * moe


def prefill_flops(cfg, batch: int, prompt_len: int) -> float:
    """A prefill of ``batch`` rows of ``prompt_len`` tokens (causal),
    expanded MLA."""
    m = _dims(cfg)
    H, dn, dr, dv = m["H"], m["dn"], m["dr"], m["dv"]
    per_token = 2.0 * m["L"] * _attn_weights(m) + _ffn_flops_per_token(m)
    pairs = prompt_len * (prompt_len + 1) / 2
    attn = 2.0 * m["L"] * H * (dn + dr + dv) * pairs
    return batch * (per_token * prompt_len + attn + 2.0 * m["d"] * m["V"])


def decode_flops(cfg, batch: int, context: int) -> float:
    """One decode step, absorbed MLA."""
    m = _dims(cfg)
    d, H, r, dn, dr, dv = (m[x] for x in ("d", "H", "r", "dn", "dr", "dv"))
    proj = d * H * (dn + dr) + d * (r + dr) + H * dn * r + H * r * dv \
        + H * dv * d
    attn = H * (r + dr) * context + H * r * context
    per_token = 2.0 * m["L"] * (proj + attn) + _ffn_flops_per_token(m)
    return batch * (per_token + 2.0 * d * m["V"])


def experts_touched(cfg, batch: int) -> float:
    """Held experts a step of ``batch`` tokens reaches, in expectation
    under uniform top-k-of-E routing."""
    m = _dims(cfg)
    return m["n"] * (1.0 - ((m["E"] - m["k"]) / m["E"]) ** batch)


def _weight_bytes(cfg, experts) -> float:
    p = _parts(cfg)
    m = _dims(cfg)
    held = (m["L"] - 1) * experts * _expert(m)
    rest = parameters(cfg) - p["experts"] - p["router"] - p["embed"]
    return BYTES * (rest + held) + ROUTER_BYTES * p["router"]


def _cache_bytes(cfg, batch: int, positions: int) -> float:
    m = _dims(cfg)
    return BYTES * m["L"] * batch * positions * (m["r"] + m["dr"])


def decode_bytes(cfg, batch: int, context: int) -> float:
    return (_weight_bytes(cfg, experts_touched(cfg, batch))
            + _cache_bytes(cfg, batch, context))


def prefill_bytes(cfg, batch: int, prompt_len: int) -> float:
    return (_weight_bytes(cfg, _dims(cfg)["n"])
            + _cache_bytes(cfg, batch, prompt_len))


def job_contexts(prompt_len: int, gen_tokens: int):
    """Context read by each decode step of a job: step i writes position
    prompt_len + i and attends over prompt_len + i + 1 positions."""
    return [prompt_len + i + 1 for i in range(gen_tokens - 1)]
