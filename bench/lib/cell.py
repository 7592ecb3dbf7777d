"""Look a cell up in `BENCHMARK.json` and load what it names.

Nothing here knows a particular configuration, model family, traffic mix
or metric: each is found by the name the cell gives it.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
import types

ROOT = pathlib.Path(__file__).resolve().parents[2]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict            # bench/configs/<config>.json
    family: types.ModuleType    # bench/families/<config's family>.py
    traffic_name: str
    traffic: dict           # bench/traffic/<traffic>.json
    end_to_end: list        # metric entries of BENCHMARK.json that apply
    per_layer: list
    root: pathlib.Path

    def limits(self) -> dict:
        """Limits of the numbers `correct` compares: the configuration's,
        overridden by the traffic mix's where it states its own."""
        return {**self.config.get("limits", {}),
                **self.traffic.get("limits", {})}


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell_name: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in e2e_names


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; cells: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    family = load_module("families", config["family"], root)
    traffic = json.loads(_find(root, "traffic", f"{w['traffic']}.json")
                         .read_text())
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, set())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, family=family, traffic_name=w["traffic"],
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                root=root)


def _find(root: pathlib.Path, kind: str, file: str) -> pathlib.Path:
    """bench/<kind>/<file> under `root`, else under this checkout (a
    cell kept elsewhere may bring only its own new files)."""
    for r in (root, ROOT):
        path = r / "bench" / kind / file
        if path.is_file():
            return path
    raise FileNotFoundError(f"no bench/{kind}/{file} under {root} or "
                            f"{ROOT}")


def load_module(kind: str, name: str, root: pathlib.Path = ROOT):
    """bench/<kind>/<name>.py as a module (file names may hold dots)."""
    path = _find(root, kind, f"{name}.py")
    mod_name = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    key = f"{mod_name}@{path}"
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod
