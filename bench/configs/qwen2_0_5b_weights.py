"""Random qwen2 weights made from the seed, and their layout in the engine.

``make(seed, cfg)`` builds every weight on the device in one jitted call,
in bfloat16 (the type they are served in), in a plain layout that the
reference reads: per-layer tensors stacked on a leading layer axis,
projection matrices as (fan_in, fan_out).  ``to_engine`` reshapes them into
the parameter tree that ``repro.models`` serves (``embed.tokens``,
``blocks.{ln1,attn,ln2,mlp}``, ``final_norm``); it is the one place that
knows the program's layout.  ``arch(cfg)`` is the program's model that the
engine must serve for the configuration.
"""

from __future__ import annotations

import functools

import numpy as np


def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "f": cfg["intermediate_size"],
            "L": cfg["num_hidden_layers"], "H": h,
            "KV": cfg["num_key_value_heads"], "hd": d // h,
            "V": cfg["vocab_size"]}


# the published widths the file states, by the program's field names
_WIDTHS = {"d_model": "hidden_size", "n_heads": "num_attention_heads",
           "n_kv_heads": "num_key_value_heads", "d_ff": "intermediate_size",
           "tie_embeddings": "tie_word_embeddings",
           "qkv_bias": "attention_bias", "rope_theta": "rope_theta"}


def arch(cfg: dict):
    """The ``ArchConfig`` the engine must serve for ``cfg``: the program's
    ``deployment.arch`` at the file's depth and vocabulary.  Raises where
    any other width the file states is not the program's."""
    import dataclasses

    from repro.configs import get_config

    base = get_config(cfg["deployment"]["arch"])
    off = [(f, k) for f, k in _WIDTHS.items() if getattr(base, f) != cfg[k]]
    if off:
        has = ", ".join(f"{f}={getattr(base, f)!r}" for f, _ in off)
        states = ", ".join(f"{k}={cfg[k]!r}" for _, k in off)
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


@functools.lru_cache(maxsize=None)
def _maker(shape_key: tuple):
    import jax
    import jax.numpy as jnp

    m = dict(shape_key)
    d, f, L, H, KV, hd, V = (m[k] for k in ("d", "f", "L", "H", "KV", "hd",
                                            "V"))
    bf16 = jnp.bfloat16

    def normal(key, shape, std):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(bf16)

    def make(key):
        k = iter(jax.random.split(key, 16))
        return {
            "embed": normal(next(k), (V, d), 0.02),
            "final_norm": (1.0 + normal(next(k), (d,), 0.1)).astype(bf16),
            "ln1": (1.0 + normal(next(k), (L, d), 0.1)).astype(bf16),
            "ln2": (1.0 + normal(next(k), (L, d), 0.1)).astype(bf16),
            "wq": normal(next(k), (L, d, H * hd), d ** -0.5),
            "wk": normal(next(k), (L, d, KV * hd), d ** -0.5),
            "wv": normal(next(k), (L, d, KV * hd), d ** -0.5),
            "bq": normal(next(k), (L, H * hd), 0.1),
            "bk": normal(next(k), (L, KV * hd), 0.1),
            "bv": normal(next(k), (L, KV * hd), 0.1),
            "wo": normal(next(k), (L, H * hd, d), (H * hd) ** -0.5),
            "w_gate": normal(next(k), (L, d, f), d ** -0.5),
            "w_up": normal(next(k), (L, d, f), d ** -0.5),
            "w_down": normal(next(k), (L, f, d), f ** -0.5),
        }

    return jax.jit(make)


def make(seed: int, cfg: dict):
    return _maker(tuple(sorted(dims(cfg).items())))(key_for(seed))


def to_engine(w, cfg: dict):
    """The weights as the engine's parameter tree (no copy beyond reshapes)."""
    m = dims(cfg)
    L, d, H, KV, hd = m["L"], m["d"], m["H"], m["KV"], m["hd"]
    return {
        "embed": {"tokens": w["embed"]},
        "blocks": {
            "ln1": {"scale": w["ln1"]},
            "ln2": {"scale": w["ln2"]},
            "attn": {
                "wq": w["wq"].reshape(L, d, H, hd),
                "wk": w["wk"].reshape(L, d, KV, hd),
                "wv": w["wv"].reshape(L, d, KV, hd),
                "wo": w["wo"].reshape(L, H, hd, d),
                "bq": w["bq"].reshape(L, H, hd),
                "bk": w["bk"].reshape(L, KV, hd),
                "bv": w["bv"].reshape(L, KV, hd),
            },
            "mlp": {"wi_gate": w["w_gate"], "wi_up": w["w_up"],
                    "wo": w["w_down"]},
        },
        "final_norm": {"scale": w["final_norm"]},
    }
