"""Tiny overrides of each configuration and traffic mix, for CPU runs."""

# the published widths at 4 layers and a 16,384-token vocabulary: a model
# small enough for the CPU whose logits spread like the full model's, so
# that the cell's limit means the same here
QWEN = dict(
    config={"num_hidden_layers": 4, "vocab_size": 16384},
    traffic={"batch_size": 2, "prompt_len": 16, "gen_tokens": 8,
             "max_len": 32, "requests_per_call": 4, "check_requests": 4,
             "check_block": 3})


def overrides(cell_name: str) -> dict:
    return QWEN


def serve_this_model(monkeypatch, cell_name: str) -> None:
    """Make the engine serve the tiny model the overrides describe (the
    engine offers only the published model or its own reduced twin)."""
    import dataclasses

    from repro.configs import get_config
    from repro.serving import ServeEngineConfig

    cfg = QWEN["config"]
    arch = dataclasses.replace(get_config("qwen2-0.5b"),
                               n_layers=cfg["num_hidden_layers"],
                               vocab_size=cfg["vocab_size"])
    monkeypatch.setattr(ServeEngineConfig, "arch_config", lambda self: arch)
