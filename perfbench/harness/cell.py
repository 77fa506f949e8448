"""A cell by name: its configuration, traffic mix, deck and metrics.

Every piece is a file of its own, found by name, so a later cell or
metric is added as files and entries and edits nothing here:

    perfbench/configs/<config>.json          sizes, source, guarantees
    perfbench/configs/<config>.py            its table, built from a seed
    perfbench/traffic/<traffic>.json         parameters of the mix
    perfbench/traffic/decks/<config>.<traffic>.jsonl   the mix's deck
    perfbench/metrics/<metric>.py            one reader per metric
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = "perfbench"


def load_module(path: Path, name: str):
    """Import one file of the benchmark by its path (names hold '-' and '.')."""
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    kind: str  # "end_to_end" | "per_layer"
    reader: object  # module with read(record) -> float | None


@dataclasses.dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: list[Metric]

    @classmethod
    def load(cls, root: Path, workload: str, *, check_listed: bool = True) -> "Cell":
        """The cell ``<config>.<traffic>`` as BENCHMARK.json lists it.  With
        ``check_listed=False`` a cell not yet listed is found by its files
        alone (deck drawing, tests), with no metrics."""
        root = Path(root)
        bench_dir = root / BENCH_DIR
        entry = None
        spec = {}
        bench_file = root / "BENCHMARK.json"
        if bench_file.is_file():
            spec = json.loads(bench_file.read_text())
            entry = next((w for w in spec["workloads"] if w["name"] == workload), None)
        if entry is None:
            if check_listed:
                raise KeyError(f"workload {workload!r} is not in BENCHMARK.json")
            config_name, _, traffic_name = workload.partition(".")
            entry = {"name": workload, "config": config_name, "traffic": traffic_name,
                     "chips": 1}
        config = json.loads((bench_dir / "configs" / f"{entry['config']}.json").read_text())
        traffic = json.loads((bench_dir / "traffic" / f"{entry['traffic']}.json").read_text())
        metrics = []
        for kind in ("end_to_end", "per_layer"):
            for m in spec.get(kind, []):
                if workload not in m.get("workloads", [workload]):
                    continue
                reader = load_module(bench_dir / "metrics" / f"{m['name']}.py",
                                     f"perfbench_metric_{len(metrics)}")
                metrics.append(Metric(m["name"], m["unit"], kind, reader))
        return cls(root, workload, int(entry["chips"]), config, traffic, metrics)

    @property
    def deck_path(self) -> Path:
        return (self.root / BENCH_DIR / "traffic" / "decks"
                / f"{self.config['name']}.{self.traffic['name']}.jsonl")

    def build_table(self, seed: int, num_partitions: int | None = None,
                    rows_per_partition: int | None = None):
        """The configuration's table for ``seed``, at its own size unless
        told otherwise (tests)."""
        maker = load_module(self.root / BENCH_DIR / "configs" / f"{self.config['name']}.py",
                            "perfbench_table_" + self.config["name"].replace("-", "_"))
        return maker.build(
            seed,
            num_partitions or self.config["num_partitions"],
            rows_per_partition or self.config["rows_per_partition"],
        )
