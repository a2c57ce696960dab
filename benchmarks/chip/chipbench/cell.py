"""A benchmark cell, found by name from data files.

``BENCHMARK.json`` at the root names the cell's configuration and
traffic mix; the configuration's file is the one its entry names, the
mix is ``benchmarks/chip/traffic/<traffic>.json`` and each per-layer
metric is read by ``benchmarks/chip/metrics/<metric>.py``.  A later
change adds a configuration, a mix or a metric by adding files and
entries, never by editing one that exists.
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path("benchmarks") / "chip"


@dataclasses.dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list
    per_layer: list

    @property
    def bench_dir(self) -> Path:
        return self.root / BENCH_DIR

    @property
    def lane(self) -> str:
        return self.config["lane"]

    @property
    def batch(self) -> int:
        return self.config["batch"]

    def bases_per_item(self) -> int:
        """Read bases one valid item carries: both mates of a pair, or the
        whole long read."""
        if self.lane == "pairs":
            return 2 * self.traffic["read_len"]
        return self.traffic["read_len"]

    def program_numbers(self, control: bool = False) -> dict:
        """The configuration's seed-map, pipeline and long-read numbers,
        with the control's overrides when ``control``."""
        over = self.config.get("control", {}) if control else {}
        return {k: {**self.config[k], **over.get(k, {})}
                for k in ("seedmap", "pipeline", "long_read")}

    def reference_params(self) -> dict:
        """The numbers the plain reference computes with (no control)."""
        sm, pipe = self.config["seedmap"], self.config["pipeline"]
        return {
            "index": {"seed_len": pipe["seed_len"],
                      "seeds_per_read": pipe["seeds_per_read"],
                      "hash_seed": sm["hash_seed"],
                      "table_bits": sm["table_bits"],
                      "max_locations": sm["max_locations"],
                      "max_locs_per_seed": pipe["max_locs_per_seed"]},
            "pipeline": pipe,
            "long_read": {**pipe, **self.config["long_read"]},
        }

    def store_key(self) -> str:
        """Directory name of the index store: every number the store
        depends on, so configurations that share an index share it."""
        keyed = {k: self.config[k] for k in
                 ("genome", "seedmap", "pipeline", "long_read")}
        digest = hashlib.sha256(
            json.dumps(keyed, sort_keys=True).encode()).hexdigest()[:16]
        return f"index-{digest}"


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, workload: str) -> Cell:
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return Cell(
        root=root, name=workload, chips=w["chips"],
        config_name=w["config"], config=_load_json(root / cfg_entry["file"]),
        traffic_name=w["traffic"],
        traffic=_load_json(root / BENCH_DIR / "traffic" /
                           f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)])


def load_reader(cell: Cell, metric: str):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = cell.bench_dir / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def load_peaks(cell: Cell, device_kind: str) -> dict:
    """The device's published peaks; a device not in the table is an
    error, never a default."""
    peaks = _load_json(cell.bench_dir / "peaks.json")
    if device_kind not in peaks:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json (has {sorted(peaks)})")
    return peaks[device_kind]
