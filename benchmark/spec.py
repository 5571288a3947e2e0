"""What a cell is made of, found by name: ``BENCHMARK.json`` names a cell's
configuration and traffic mix, and every other piece is a file under
``benchmark/`` that carries that name. Nothing here knows a cell, a
configuration, a mix or a metric by name."""

from __future__ import annotations

import importlib
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class SpecError(SystemExit):
    def __init__(self, msg: str):
        super().__init__(f"benchmark: {msg}")


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise SpecError(f"cannot read {path}: {e}") from None


class Cell:
    """One entry of ``workloads`` with its configuration, its mix and the
    metrics it reports."""

    def __init__(self, bench_file: str, workload: str):
        self.bench = _load_json(bench_file)
        root = os.path.dirname(os.path.abspath(bench_file))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SpecError(f"no workload {workload!r} in {bench_file}; "
                            f"there are {sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfg = {c["name"]: c for c in self.bench["configs"]}[self.entry["config"]]
        self.config = _load_json(os.path.join(root, cfg["file"]))
        self.config_name = cfg["name"]
        self.mix_name = self.entry["traffic"]
        # a test's own BENCHMARK file may keep its mixes beside it
        mixes = os.path.join(root, self.bench["traffic_dir"]) \
            if "traffic_dir" in self.bench else os.path.join(HERE, "traffic")
        self.mix = _load_json(os.path.join(mixes, self.mix_name + ".json"))

    def _mine(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    @property
    def end_to_end(self) -> list[dict]:
        return [m for m in self.bench["end_to_end"] if self._mine(m)]

    @property
    def per_layer(self) -> list[dict]:
        return [m for m in self.bench["per_layer"] if self._mine(m)]


def query_text(suite_path: str) -> str:
    """A query or template text under ``benchmark/queries/``."""
    path = os.path.join(HERE, "queries", suite_path)
    try:
        with open(path) as f:
            return f.read()
    except OSError as e:
        raise SpecError(f"cannot read query file {path}: {e}") from None


def loader(generator: str):
    """``benchmark/loaders/<generator>.py``, by the config's ``generator``."""
    try:
        return importlib.import_module(f"benchmark.loaders.{generator}")
    except ModuleNotFoundError as e:
        if e.name != f"benchmark.loaders.{generator}":
            raise
        raise SpecError(f"no loader for generator {generator!r} "
                        f"(benchmark/loaders/{generator}.py)") from None


def _reader(folder: str, metric: str):
    path = os.path.join(HERE, folder, metric + ".py")
    if not os.path.exists(path):
        raise SpecError(f"metric {metric!r} has no reader {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{folder}." + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def layer_reader(metric: str):
    """``read(run)`` of ``benchmark/layer_metrics/<metric>.py``."""
    return _reader("layer_metrics", metric)


def end_to_end_reader(metric: str):
    """``read(run)`` of ``benchmark/end_to_end/<metric>.py``."""
    return _reader("end_to_end", metric)


def peaks(device_kind: str) -> dict:
    table = _load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table["devices"]:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        f"benchmark/peaks.json: no peak is assumed")
    return table["devices"][device_kind]
