"""The program's solver for a configuration file, built as a user builds it:
through the scenario registry, with every size taken from the file."""

from __future__ import annotations


def build_solver(cfg: dict, kernel_impl: str):
    from repro.configs.registry import resolve_scenario

    mat = cfg["materials"]
    return resolve_scenario(cfg["scenario"]).build(
        grid=tuple(cfg["grid"]), order=int(cfg["order"]), extent=tuple(cfg["extent"]),
        cp=tuple(mat["cp"]), cs=tuple(mat["cs"]), rho=tuple(mat["rho"]),
        dtype=cfg["dtype"], kernel_impl=kernel_impl,
    )
