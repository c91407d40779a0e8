"""Random DeepSeek-V2-Lite weights made from the seed, and their layout in
the engine.

``make(seed, cfg)`` builds every weight on the device in one jitted call,
in bfloat16 (the type they are served in; the router, whose softmax is
float32, in float32 as the program holds it), already in the shapes the
engine serves, so that ``to_engine`` only regroups keys and copies
nothing: set-up holds the engine's own initial weights beside these.  The
reference reads the same flat dict.  Layer 0 (dense) has keys of its own
(``dense.*``); the MoE layers' tensors are stacked on a leading layer
axis.  Projections are ``(fan_in..., fan_out...)``: ``wq`` (d, H, nope +
rope), ``wkv_a`` (d, kv_lora_rank + rope), ``wk_b`` (kv_lora_rank, H,
nope), ``wv_b`` (kv_lora_rank, H, v), ``wo`` (H, v, d); the router (d,
64), the held experts (8, d, f) and (8, f, d), the shared experts one
SwiGLU of width n_shared x f.  ``arch(cfg)`` is the program's model that
the engine must serve for the configuration.
"""

from __future__ import annotations

import functools

import numpy as np


def dims(cfg: dict) -> dict:
    ep = cfg["expert_parallel"]
    return {"d": cfg["hidden_size"], "F": cfg["intermediate_size"],
            "L": cfg["num_hidden_layers"], "H": cfg["num_attention_heads"],
            "r": cfg["kv_lora_rank"], "dn": cfg["qk_nope_head_dim"],
            "dr": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
            "E": ep["published_n_routed_experts"],
            "n": cfg["n_routed_experts"], "first": ep["rank"]
            * cfg["n_routed_experts"], "k": cfg["num_experts_per_tok"],
            "f": cfg["moe_intermediate_size"],
            "S": cfg["n_shared_experts"], "V": cfg["vocab_size"]}


# what the program implements, of the published keys it has no field for
_FIXED = {"q_lora_rank": None, "attention_bias": False, "hidden_act": "silu",
          "first_k_dense_replace": 1, "moe_layer_freq": 1, "n_group": 1,
          "topk_group": 1, "topk_method": "greedy", "scoring_func": "softmax",
          "routed_scaling_factor": 1, "rms_norm_eps": 1e-6}


def _widths(base, cfg: dict) -> list:
    """(program field, its value, the file's value) for every width the
    file states and the program has."""
    m, moe, y, rs = base.mla, base.moe, base.rope_scaling, cfg["rope_scaling"]
    n = dims(cfg)
    return [
        ("d_model", base.d_model, n["d"]), ("n_heads", base.n_heads, n["H"]),
        ("n_kv_heads", base.n_kv_heads, cfg["num_key_value_heads"]),
        ("d_ff", base.d_ff, n["F"]),
        ("tie_embeddings", base.tie_embeddings, cfg["tie_word_embeddings"]),
        ("rope_theta", base.rope_theta, cfg["rope_theta"]),
        ("mla.kv_lora_rank", m.kv_lora_rank, n["r"]),
        ("mla.qk_nope_head_dim", m.qk_nope_head_dim, n["dn"]),
        ("mla.qk_rope_head_dim", m.qk_rope_head_dim, n["dr"]),
        ("mla.v_head_dim", m.v_head_dim, n["dv"]),
        ("moe.n_experts", moe.n_experts, n["E"]),
        ("moe.held", moe.held, n["n"]),
        ("held from expert", 0, n["first"]),
        ("moe.top_k", moe.top_k, n["k"]),
        ("moe.d_expert", moe.d_expert, n["f"]),
        ("moe.n_shared", moe.n_shared, n["S"]),
        ("moe.renormalize", moe.renormalize, cfg["norm_topk_prob"]),
        ("moe.dropless", moe.dropless, True),
        ("moe.first_layer_dense", moe.first_layer_dense, True),
        ("rope_scaling.factor", y.factor, rs["factor"]),
        ("rope_scaling.original_max_position_embeddings",
         y.original_max_position_embeddings,
         rs["original_max_position_embeddings"]),
        ("rope_scaling.beta_fast", y.beta_fast, rs["beta_fast"]),
        ("rope_scaling.beta_slow", y.beta_slow, rs["beta_slow"]),
        ("rope_scaling.mscale", y.mscale, rs["mscale"]),
        ("rope_scaling.mscale_all_dim", y.mscale_all_dim,
         rs["mscale_all_dim"]),
    ]


def arch(cfg: dict):
    """The ``ArchConfig`` the engine must serve for ``cfg``: the program's
    ``deployment.arch`` at the file's depth and vocabulary.  Raises where
    any other width or setting the file states is not the program's."""
    import dataclasses

    from repro.configs import get_config

    base = get_config(cfg["deployment"]["arch"])
    off = [(f, a, b) for f, a, b in _widths(base, cfg) if a != b]
    off += [(k, v, cfg[k]) for k, v in _FIXED.items() if cfg[k] != v]
    if cfg["rope_scaling"]["type"] != "yarn":
        off.append(("rope_scaling.type", "yarn", cfg["rope_scaling"]["type"]))
    if off:
        has = ", ".join(f"{f}={a!r}" for f, a, _ in off)
        states = ", ".join(f"{f}={b!r}" for f, _, b in off)
        raise ValueError(f"{base.name} has {has}; the configuration states "
                         f"{states}")
    return dataclasses.replace(base, n_layers=cfg["num_hidden_layers"],
                               vocab_size=cfg["vocab_size"])


def key_for(seed: int):
    """A PRNG key that depends on every bit of ``seed`` (JAX's own
    ``PRNGKey`` keeps only the low 32 bits)."""
    import jax

    words = np.random.SeedSequence([seed, 0x3E16]).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0]) & 0x7FFFFFFF),
                              int(words[1]) & 0x7FFFFFFF)


def shapes(cfg: dict) -> dict:
    """Every weight's shape, by its key in the flat dict."""
    m = dims(cfg)
    d, F, H, r, dn, dr, dv = (m[k] for k in ("d", "F", "H", "r", "dn", "dr",
                                             "dv"))
    lm, n, f, sf = m["L"] - 1, m["n"], m["f"], m["S"] * m["f"]
    attn = {"ln1": (d,), "ln2": (d,), "wq": (d, H, dn + dr),
            "wkv_a": (d, r + dr), "kv_norm": (r,), "wk_b": (r, H, dn),
            "wv_b": (r, H, dv), "wo": (H, dv, d)}
    out = {"embed": (m["V"], d), "unembed": (d, m["V"]),
           "final_norm": (d,)}
    out.update({f"dense.{k}": s for k, s in attn.items()})
    out.update({"dense.w_gate": (d, F), "dense.w_up": (d, F),
                "dense.w_down": (F, d)})
    out.update({k: (lm,) + s for k, s in attn.items()})
    out.update({"router": (lm, d, m["E"]), "e_gate": (lm, n, d, f),
                "e_up": (lm, n, d, f), "e_down": (lm, n, f, d),
                "s_gate": (lm, d, sf), "s_up": (lm, d, sf),
                "s_down": (lm, sf, d)})
    return out


# weights drawn 1 + 0.1 N(0, 1) (norm scales) or std 0.02 (embedding);
# every other one std fan_in**-0.5, with this many leading fan-in axes
# after any stacking axes (the rest are fan-out)
_NORMS = ("ln1", "ln2", "kv_norm", "final_norm")
FAN_IN = {"wq": (1, 2), "wkv_a": (1, 1), "wk_b": (1, 2), "wv_b": (1, 2),
          "wo": (2, 1), "w_gate": (1, 1), "w_up": (1, 1), "w_down": (1, 1),
          "router": (1, 1), "e_gate": (1, 1), "e_up": (1, 1),
          "e_down": (1, 1), "s_gate": (1, 1), "s_up": (1, 1),
          "s_down": (1, 1), "unembed": (1, 1)}


def kind(name: str) -> str:
    return name.rsplit(".", 1)[-1]


def fan_in(name: str, shape) -> int:
    n_in, n_out = FAN_IN[kind(name)]
    return int(np.prod(shape[len(shape) - n_in - n_out:len(shape) - n_out]))


@functools.lru_cache(maxsize=None)
def _maker(shape_key: tuple):
    import jax
    import jax.numpy as jnp

    table = dict(shape_key)
    bf16 = jnp.bfloat16

    def draw(key, name, shape):
        z = jax.random.normal(key, shape, jnp.float32)
        if kind(name) in _NORMS:
            return (1.0 + 0.1 * z).astype(bf16)
        std = 0.02 if name == "embed" else fan_in(name, shape) ** -0.5
        # the router stays float32, as the program holds it
        return z * std if name == "router" else (z * std).astype(bf16)

    def make(key):
        keys = jax.random.split(key, len(table))
        return {name: draw(k, name, shape)
                for k, (name, shape) in zip(keys, sorted(table.items()))}

    return jax.jit(make)


def make(seed: int, cfg: dict):
    table = tuple(sorted(shapes(cfg).items()))
    return _maker(table)(key_for(seed))


def _attn(w, p: str) -> dict:
    return {"wq": w[p + "wq"], "wkv_a": w[p + "wkv_a"],
            "kv_norm": {"scale": w[p + "kv_norm"]}, "wk_b": w[p + "wk_b"],
            "wv_b": w[p + "wv_b"], "wo": w[p + "wo"]}


def to_engine(w, cfg: dict):
    """The weights as the engine's parameter tree (the same arrays)."""
    return {
        "embed": {"tokens": w["embed"], "unembed": w["unembed"]},
        "dense_block": {
            "ln1": {"scale": w["dense.ln1"]},
            "attn": _attn(w, "dense."),
            "ln2": {"scale": w["dense.ln2"]},
            "mlp": {"wi_gate": w["dense.w_gate"], "wi_up": w["dense.w_up"],
                    "wo": w["dense.w_down"]},
        },
        "blocks": {
            "ln1": {"scale": w["ln1"]},
            "attn": _attn(w, ""),
            "ln2": {"scale": w["ln2"]},
            "moe": {"router": w["router"], "wi_gate": w["e_gate"],
                    "wi_up": w["e_up"], "wo": w["e_down"],
                    "shared": {"wi_gate": w["s_gate"], "wi_up": w["s_up"],
                               "wo": w["s_down"]}},
        },
        "final_norm": {"scale": w["final_norm"]},
    }
