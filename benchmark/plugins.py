"""Finding the files a cell is made of. Readers, backends and checks
are plain files found by name, `<root>/<kind>/<name>.py`, looked up in
every directory of `paths` and then in `benchmark/` itself, so a later
PR adds one by adding a file.

A per-layer reader is `layer_metrics/<metric name>.py` with `read(ctx)`
and the constants UNIT, LAYER, MOVES, SOURCE; a reader that finds
nothing to read returns None and the metric is left out of the line."""

from __future__ import annotations

import importlib.util
import os
import re


def load(kind: str, name: str, roots: list):
    for root in roots:
        path = os.path.join(root, kind, name + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"benchmark_{kind}_" + re.sub(r"\W", "_", name), path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            return module
    raise FileNotFoundError(f"no {kind}/{name}.py in {roots}")


def metric(ctx: dict, name: str):
    """One reader's value; lets a reader build on another."""
    return load("layer_metrics", name, ctx["reader_roots"]).read(ctx)
