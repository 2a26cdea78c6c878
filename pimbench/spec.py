"""Resolve a cell of ``BENCHMARK.json`` to the files that define it."""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
#: backends on which a workload with a native command path launches
#: HBM-PIM CRF command streams (``repro.core.hbmpim``)
HBM_PIM = ("hbmpim", "hbmpim_cmd")


@dataclass
class Cell:
    name: str
    chips: int
    config: dict              # the configuration file
    traffic: dict             # the traffic mix file
    end_to_end: List[dict]    # BENCHMARK.json's metrics
    per_layer: List[dict]

    def dpu(self) -> dict:
        """DPUConfig fields: the configuration's, with the traffic mix's
        MRAM image size."""
        return {**self.config["dpu"], "mram_bytes": self.traffic["mram_bytes"]}

    def expected_path(self) -> Path:
        return HERE / "expected" / f"{self.name}.json"

    def expected(self) -> dict:
        """Pinned simulated statistics, keyed by data seed (as a string)."""
        return json.loads(self.expected_path().read_text())["data_seeds"]

    def reference(self) -> ModuleType:
        return load_module(HERE / "reference" / f"{self.traffic['workload']}.py")

    def timing_model(self) -> ModuleType:
        """The plain timing model that the check runs sampled launches on:
        ``reference/<timing_model>.py`` as the configuration names it,
        ``dpu`` where it names none."""
        name = self.config.get("timing_model", "dpu")
        return load_module(HERE / "reference" / f"{name}.py")

    def warmup(self) -> ModuleType:
        """How set-up warms the program's executables, as the cell's
        backend launches them: ``warmup/crf.py`` on an HBM-PIM backend
        for a workload whose reference gives its CRF command programs
        (``programs``: GEMVS takes its all-bank command path there),
        ``warmup/kernel.py``, the workload's DPU kernel, otherwise."""
        crf = (self.config["dpu"].get("backend") in HBM_PIM
               and hasattr(self.reference(), "programs"))
        return load_module(HERE / "warmup" / ("crf.py" if crf else "kernel.py"))


def load_module(path: Path) -> ModuleType:
    """Import one file by path; the name of a metric may hold dots."""
    spec = importlib.util.spec_from_file_location(
        f"pimbench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> ModuleType:
    return load_module(HERE / "metrics" / f"{name}.py")


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; cells: "
                       f"{', '.join(sorted(cells))}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads(
            (HERE / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])
