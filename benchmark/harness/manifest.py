"""Find a cell's files by name.

A cell ``<config>.<mix>`` is described by data files, all under
``benchmark/``: ``cells/<cell>.json``, ``configs/<config>.json``,
``traffic/<mix>.json`` and, per metric, ``end_to_end/<metric>.json`` or
``layer_metrics/<metric>.json`` (each naming a reader in its ``readers/``). ``BENCHMARK.json`` at the
root of the checkout says which metrics a cell reports.

A configuration's file may name, for a block other than the dense one,
the module that counts its shapes (``"shapes"``: ``harness/<module>.py``)
and its plain reference (``"reference"``: ``reference/<module>.py``);
where it names none they are ``harness/shapes.py`` and
``reference/forward.py``.
"""

from __future__ import annotations

import dataclasses
import importlib
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


def metric_files(kind: str) -> list:
    """The names of every metric that has a file, an entry of
    ``BENCHMARK.json`` or not, in order."""
    d = os.path.join(BENCH_DIR, METRIC_DIRS[kind])
    return sorted(f[:-len(".json")] for f in os.listdir(d)
                  if f.endswith(".json"))


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


# what a configuration's shape counts give (``harness/shapes.py`` is the
# dense block's and the default). A function a module cannot give for its
# block raises NotImplementedError when called; it never guesses.
SHAPES_INTERFACE = ("weight_bytes", "kv_bytes_per_token", "pool_bytes",
                    "decode_step_bytes", "decode_step_flops",
                    "prefill_flops")
REFERENCE_INTERFACE = ("logits_at",)


def _load_named(package: str, config: dict, key: str, default: str,
                interface: tuple):
    """``<package>/<config[key]>.py`` as the module ``<package>.<name>``
    (``default`` where the configuration's file has no such key)."""
    name = config.get(key, default)
    path = os.path.join(BENCH_DIR, package, f"{name}.py")
    if not (isinstance(name, str) and name.isidentifier()
            and os.path.isfile(path)):
        raise ManifestError(
            f"a configuration names {key!r}: {name!r}, and there is no "
            f"{path}")
    try:
        mod = importlib.import_module(f"{package}.{name}")
    except ImportError as e:
        raise ManifestError(f"{path}: {e}") from e
    missing = [f for f in interface if not callable(getattr(mod, f, None))]
    if missing:
        raise ManifestError(f"{path} lacks {', '.join(missing)}")
    return mod


def shapes_of(config: dict):
    """The module that counts ``config``'s bytes and operations: the
    ``harness/<module>.py`` its file names under ``"shapes"``, else
    ``harness/shapes.py``."""
    return _load_named(__package__, config, "shapes", "shapes",
                       SHAPES_INTERFACE)


def reference_of(config: dict):
    """``config``'s plain reference: the ``reference/<module>.py`` its
    file names under ``"reference"``, else ``reference/forward.py``. It
    has ``logits_at(cfg, params, tokens, positions)``."""
    return _load_named("reference", config, "reference", "forward",
                       REFERENCE_INTERFACE)
