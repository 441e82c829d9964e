"""Find a benchmark part by its name: ``bench/<kind>/<name>.py``.

Clip patterns (``patterns``), arrival policies (``arrivals``), metric
readers (``metrics``) and network descriptions (``networks``) each live
in a file of their own, named as the traffic file, ``BENCHMARK.json`` or
the configuration names them, so that a later change adds a file and
edits none.
"""
from __future__ import annotations

import functools
import importlib.util
import pathlib
import re

__all__ = ["load", "network"]

def load(root: pathlib.Path, kind: str, name: str):
    """The module ``<root>/bench/<kind>/<name>.py``."""
    path = pathlib.Path(root) / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + re.sub(r"\W", "_", name), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@functools.lru_cache(maxsize=16)
def _network(root: str, name: str):
    return load(pathlib.Path(root), "networks", name)


def network(cfg: dict):
    """The network description ``bench/networks/<topology>.py`` that the
    configuration names (``chain`` where it names none), read from the
    tree ``bench.run.cell_plan`` read the configuration from
    (``cfg["bench_root"]``); a configuration without it raises."""
    if "bench_root" not in cfg:
        raise KeyError(f"configuration {cfg.get('name')!r} has no bench_root:"
                       " read it with bench.run.cell_plan")
    return _network(cfg["bench_root"], cfg.get("topology", "chain"))
