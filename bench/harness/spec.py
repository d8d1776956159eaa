"""Find the benchmark's pieces by name.

``BENCHMARK.json`` (at the checkout's root) names the configurations, the
cells and the metrics. Everything that belongs to one of them sits in a
file of its own under the benchmark directory, found by its name:

    configs/<config>.json        a deployment: data, guarantees, service
    datasets/<generator>.py      ``generate(data)`` -> sorted keys
    references/<reference>.py    ``expected(keys, ops)``, ``control(keys)``
    traffic/<traffic>.json       a mix's parameters: its loop and draw
    loops/<loop>.py              ``warm(svc, mix, draw)``,
                                 ``run(svc, mix, draw, seconds, span)``
    draws/<distribution>.py      ``make(mix, keys, rng)`` -> ``draw(n)``
    metrics/<metric>.py          ``read(record)`` -> number or ``None``

A later cell, mix or metric is added as files and entries; no code here
names one of them.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from types import ModuleType

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads``: a configuration under a traffic mix."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]

    def metrics(self, trace: bool) -> tuple[dict, ...]:
        """The metrics a run of this cell reports: the end-to-end ones
        untraced, the per-layer ones traced."""
        return self.per_layer if trace else self.end_to_end


class Bench:
    """``BENCHMARK.json`` at ``root`` plus the files under ``bench_dir``."""

    def __init__(self, root: pathlib.Path = ROOT,
                 bench_dir: pathlib.Path = BENCH_DIR):
        self.root = pathlib.Path(root)
        self.bench_dir = pathlib.Path(bench_dir)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self._modules: dict[pathlib.Path, ModuleType] = {}

    def _entry(self, section: str, name: str) -> dict:
        for e in self.spec[section]:
            if e["name"] == name:
                return e
        known = ", ".join(e["name"] for e in self.spec[section])
        raise KeyError(f"no {section} entry named {name!r}; known: {known}")

    def config(self, name: str) -> dict:
        entry = self._entry("configs", name)
        cfg = json.loads((self.root / entry["file"]).read_text())
        if cfg.get("name") != name:
            raise ValueError(f"{entry['file']} names {cfg.get('name')!r}, "
                             f"not {name!r}")
        return cfg

    def traffic(self, name: str) -> dict:
        mix = json.loads(
            (self.bench_dir / "traffic" / f"{name}.json").read_text())
        return dict(mix, name=name)

    def cell(self, name: str) -> Cell:
        w = self._entry("workloads", name)

        def applies(m: dict) -> bool:
            return "workloads" not in m or name in m["workloads"]

        return Cell(
            name=name, chips=int(w["chips"]),
            config=self.config(w["config"]),
            traffic=self.traffic(w["traffic"]),
            end_to_end=tuple(m for m in self.spec["end_to_end"]
                             if applies(m)),
            per_layer=tuple(m for m in self.spec["per_layer"] if applies(m)))

    def module(self, kind: str, name: str) -> ModuleType:
        """``<bench_dir>/<kind>/<name>.py``, loaded once by path (metric
        names carry dots, so these are not importable as packages)."""
        path = self.bench_dir / kind / f"{name}.py"
        mod = self._modules.get(path)
        if mod is None:
            if not path.is_file():
                raise FileNotFoundError(f"no {kind} file for {name!r}: "
                                        f"{path}")
            mod_name = f"bench_{kind}_{name}".replace(".", "_").replace(
                "-", "_")
            spec = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[mod_name] = mod
            spec.loader.exec_module(mod)
            self._modules[path] = mod
        return mod

    def reader(self, metric: str):
        return self.module("metrics", metric).read

    def reference(self, cfg: dict) -> ModuleType:
        return self.module("references", cfg["reference"])
