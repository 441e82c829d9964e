"""Find a benchmark part by its name: ``bench/<kind>/<name>.py``.

Clip patterns (``patterns``), arrival policies (``arrivals``) and metric
readers (``metrics``) each live in a file of their own, named as the
traffic file or ``BENCHMARK.json`` names them, so that a later change
adds a file and edits none.
"""
from __future__ import annotations

import importlib.util
import pathlib
import re

__all__ = ["load"]


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
