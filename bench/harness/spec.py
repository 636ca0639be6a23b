"""Finds everything a cell needs by name.

``BENCHMARK.json`` names each workload's configuration and traffic mix;
this module turns those names into files under ``bench/``:

  configs/<config>.json       sizes, dtype, engine settings, pool size
  traffic/<traffic>.json      parameters of the one traffic generator
  cells/<workload>.json       what is fixed per cell: offered rate, limits,
                              a drain of its own
  metrics/<name>.py           reader of one per-layer metric; a name with a
                              dot (``step_ms.chat``) falls back to the part
                              before the first dot (``step_ms.py``)
  references/<name>.py        the module of the family a configuration
                              names under ``reference``: its plain
                              reference, weight rules and cost counts
                              (``FAMILY_API``)
  peaks.json                  device peaks, keyed by ``device_kind``

Adding a model, a family, a mix or a metric adds a file and an entry;
nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List

# What a family module supplies, beside its plain reference:
#   check_config(config)                  refuse a configuration it does not implement
#   logits_at(params, config, tokens, at, quant=None)
#                                         f32 logits of one sequence at positions ``at``
#   leaf_std(name, shape, config)         the standard deviation a weight is drawn at
#   call_flops(config, chunks, decodes)   useful FLOPs of one mixed call
#   call_bytes(config, chunks, decodes, itemsize)
#                                         least HBM bytes of one mixed call
FAMILY_API = ("check_config", "logits_at", "leaf_std", "call_flops", "call_bytes")


class SpecError(RuntimeError):
    """A name in BENCHMARK.json has no file, or a file is malformed."""


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise SpecError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of BENCHMARK.json with every file it names loaded."""
    name: str
    chips: int
    config: dict
    traffic: dict
    cell: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    family: Any = None              # the module the configuration names
    readers: Dict[str, Callable] = field(default_factory=dict)


class Bench:
    """The benchmark rooted at ``root`` (the directory of BENCHMARK.json)."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.spec = _load_json(self.root / "BENCHMARK.json")
        self.dir = self.root / "bench"

    # ----------------------------------------------------------- lookups --
    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        known = ", ".join(w["name"] for w in self.spec["workloads"])
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(known: {known})")

    def config(self, name: str) -> dict:
        cfg = _load_json(self.dir / "configs" / f"{name}.json")
        cfg["name"] = name
        return cfg

    def traffic(self, name: str) -> dict:
        t = _load_json(self.dir / "traffic" / f"{name}.json")
        t["name"] = name
        return t

    def cell_params(self, workload: str) -> dict:
        path = self.dir / "cells" / f"{workload}.json"
        return _load_json(path) if path.is_file() else {}

    def reader(self, metric: str) -> Callable:
        """``read(ctx)`` of a per-layer metric, by file name."""
        mdir = self.dir / "metrics"
        for stem in (metric, metric.split(".")[0]):
            path = mdir / f"{stem}.py"
            if path.is_file():
                mod = _load_module(path, f"bench_metric_{stem.replace('.', '_')}")
                if not hasattr(mod, "read"):
                    raise SpecError(f"{path} defines no read(ctx)")
                return mod.read
        raise SpecError(f"no reader for per-layer metric {metric!r} "
                        f"under {mdir}")

    def reference(self, name: str):
        """The family module ``references/<name>.py``, refused where it
        lacks any function of ``FAMILY_API``."""
        path = self.dir / "references" / f"{name}.py"
        if not path.is_file():
            raise SpecError(f"missing file {path}")
        mod = _load_module(path, f"bench_reference_{name}")
        missing = [f for f in FAMILY_API if not callable(getattr(mod, f, None))]
        if missing:
            raise SpecError(f"{path} defines no {', '.join(missing)}")
        return mod

    def peaks(self, device_kind: str) -> dict:
        table = _load_json(self.dir / "peaks.json")
        if device_kind not in table:
            raise SpecError(f"device {device_kind!r} is not in bench/peaks.json "
                            f"(known: {', '.join(sorted(table))})")
        return table[device_kind]

    # ------------------------------------------------------------- cells --
    def metrics_of(self, workload: str):
        """(end_to_end, per_layer) metric entries this workload reports."""
        def applies(m):
            return "workloads" not in m or workload in m["workloads"]
        e2e = [m for m in self.spec["end_to_end"] if applies(m)]
        names = {m["name"] for m in e2e}
        per = [m for m in self.spec["per_layer"]
               if applies(m) and m["moves"] in names]
        return e2e, per

    def cell(self, workload: str) -> Cell:
        w = self.workload(workload)
        e2e, per = self.metrics_of(workload)
        config = self.config(w["config"])
        c = Cell(name=workload, chips=int(w["chips"]), config=config,
                 traffic=self.traffic(w["traffic"]),
                 cell=self.cell_params(workload),
                 end_to_end=e2e, per_layer=per,
                 family=self.reference(config["reference"]))
        c.readers = {m["name"]: self.reader(m["name"]) for m in per}
        return c

    def all_cells(self) -> List[str]:
        return [w["name"] for w in self.spec["workloads"]]


def discover(root: Path, kind: str) -> List[str]:
    """Names of the files of one kind under ``bench/`` (``configs``,
    ``traffic``, ``cells``, ``metrics``), by file name."""
    d = Path(root) / "bench" / kind
    suffix = ".py" if kind in ("metrics", "references") else ".json"
    return sorted(p.name[: -len(suffix)] for p in d.glob(f"*{suffix}")
                  if not p.name.startswith("_"))
