"""Cells at a size a CPU test run holds: the program's smoke configurations
under the benchmark's loops."""
from __future__ import annotations

import dataclasses

from bench import harness as h

SMOKE = {"mamba2-130m": "mamba2", "hymba-1.5b": "hymba"}


def model_of(arch: str) -> dict:
    """The smoke configuration in the configuration file's names."""
    from repro.configs.registry import get_smoke_config
    cfg = get_smoke_config(arch)
    full = h.load_json(h.BENCH / "configs" / f"{arch}.json")["program"]
    return {k: getattr(cfg, k) for k in full} | {"family": cfg.family}


def cell(name: str, mix: str | None = None, **traffic) -> h.Cell:
    """The benchmark's cell ``name`` with the smoke configuration of its
    model, its traffic replaced by the mix file ``mix`` if one is named,
    and the traffic's values overridden by ``traffic``."""
    c = h.find_cell(h.BENCH.parent, name)
    if mix is not None:
        c.traffic = h.load_json(h.BENCH / "traffic" / f"{mix}.json")
        c.chips = c.traffic["chips"]
    arch = c.config["program_arch"]
    return dataclasses.replace(
        c, config=dict(c.config, program=model_of(arch)),
        traffic=dict(c.traffic, **traffic))


def smoke_registry(monkeypatch) -> None:
    """Make --full and get_config hand out the smoke configurations."""
    from repro.configs import registry
    from repro.launch import train
    monkeypatch.setattr(registry, "get_config", registry.get_smoke_config)
    monkeypatch.setattr(train, "get_config", registry.get_smoke_config)


