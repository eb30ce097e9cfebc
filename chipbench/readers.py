"""Finding a per-layer metric's reader by the metric's name."""

from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_reader(name: str):
    """``chipbench/layer_metrics/<name>.py`` by path (a metric's name
    may hold dots, so it is not an importable module name)."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_layer_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
