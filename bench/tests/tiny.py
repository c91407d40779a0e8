"""Tiny sizes of a cell, for CPU runs: the ``tiny`` block of the cell's
configuration file (``{"config": {...}, "traffic": {...}}``), overrides of
the configuration and of the traffic mix."""

from harness import spec


def _cell(cell) -> spec.Cell:
    """A cell, given as one or by its name in ``BENCHMARK.json``."""
    return spec.load_cell(cell) if isinstance(cell, str) else cell


def overrides(cell) -> dict:
    """``Cell.replace`` arguments that cut ``cell`` to its tiny size."""
    cfg = _cell(cell).config
    if "tiny" not in cfg:
        raise KeyError(f"configuration {cfg['name']!r} has no 'tiny' block "
                       "of CPU sizes (config and traffic overrides)")
    return {"config": cfg["tiny"]["config"],
            "traffic": cfg["tiny"]["traffic"]}


def serve_this_model(monkeypatch, cell) -> None:
    """Make the engine serve the tiny model the overrides describe, its
    weights module's ``arch`` (the engine offers only the published model
    or its own reduced twin)."""
    from repro.serving import ServeEngineConfig

    small = _cell(cell)
    small = small.replace(**overrides(small))
    arch = spec.load_module(small.config["weights"]).arch(small.config)
    monkeypatch.setattr(ServeEngineConfig, "arch_config", lambda self: arch)
