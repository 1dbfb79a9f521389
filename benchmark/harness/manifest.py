"""Find a cell's files by name.

A cell ``<config>.<mix>`` is described by data files, all under
``benchmark/``: ``cells/<cell>.json``, ``configs/<config>.json``,
``traffic/<mix>.json`` and, per metric, ``end_to_end/<metric>.json`` or
``layer_metrics/<metric>.json`` (each naming a reader in its ``readers/``). ``BENCHMARK.json`` at the
root of the checkout says which metrics a cell reports.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)


class ManifestError(Exception):
    """A file a name points at is missing or malformed."""


def load_json(*parts: str) -> dict:
    path = os.path.join(BENCH_DIR, *parts)
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError(f"{path}: {e}") from e
    if not isinstance(doc, dict):
        raise ManifestError(f"{path}: not a JSON object")
    return doc


def load_benchmark() -> dict:
    path = os.path.join(REPO_DIR, "BENCHMARK.json")
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError(f"{path}: {e}") from e


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    cell: dict      # cells/<name>.json
    config: dict    # configs/<config>.json
    mix: dict       # traffic/<mix>.json

    @property
    def rate_rps(self) -> float:
        """The offered rate: fixed in the cell, never searched for."""
        return float(self.cell["knee_rps"]) * float(
            self.mix["arrivals"]["rate_share_of_knee"])


def load_cell(name: str) -> Cell:
    cell = load_json("cells", f"{name}.json")
    for key in ("config", "traffic", "knee_rps"):
        if key not in cell:
            raise ManifestError(f"cells/{name}.json lacks {key!r}")
    if name != f"{cell['config']}.{cell['traffic']}":
        raise ManifestError(
            f"cell {name!r} is not named <config>.<traffic> "
            f"({cell['config']}.{cell['traffic']})")
    return Cell(name, cell, load_json("configs", f"{cell['config']}.json"),
                load_json("traffic", f"{cell['traffic']}.json"))


def read_metric(kind: str, name: str, ctx):
    """One metric's value from ``ctx``, through the reader its file
    names; None where the reader found nothing to read."""
    spec = load_json(METRIC_DIRS[kind], f"{name}.json")
    return load_reader(kind, spec["reader"]).read(ctx,
                                                  **spec.get("args", {}))


def metrics_of(bench: dict, cell_name: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that ``cell_name``
    reports: those with no ``workloads`` key, or that list it."""
    return [m for m in bench.get(kind, ())
            if "workloads" not in m or cell_name in m["workloads"]]


METRIC_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def load_reader(kind: str, name: str):
    """``<end_to_end|layer_metrics>/readers/<name>.py`` as a module; it
    has ``read(ctx, **args)``, which returns a number or None."""
    path = os.path.join(BENCH_DIR, METRIC_DIRS[kind], "readers",
                        f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_reader_{name}", path)
    if spec is None or not os.path.isfile(path):
        raise ManifestError(f"no reader {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
