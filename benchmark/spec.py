"""Find a cell's pieces by name.

A cell in ``BENCHMARK.json`` names a configuration and a traffic mix; its
metrics are the entries of ``end_to_end`` and ``per_layer`` that apply to
it. Each piece is a file of its own, found by name:

  configs   the ``file`` of the configuration entry (``benchmark/configs/``)
  traffic   ``benchmark/traffic/<traffic>.json``
  drivers   ``benchmark/drivers/<driver>.py``, the mix's ``driver`` (or
            ``ps_closed_loop`` where it names none): what a run sets up,
            drives in its window and checks. It exports ``KEYS``, the mix
            keys it reads; ``check_mix(mix)``, which raises ValueError for
            a mix it cannot run; and ``run(cell, args, session, rehearse,
            control)``, which returns a ``harness.Outcome``
  metrics   ``benchmark/metrics/<metric name>.py``, whose ``read(run)``
            returns the metric's value or None when it finds nothing to read
  peaks     ``benchmark/peaks.json``, keyed by JAX's ``device_kind``

So a later PR adds a cell, a mix, a driver or a metric by adding files and
entries, and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from dataclasses import dataclass, field
from types import ModuleType
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# Keys any mix may carry; the rest are its driver's ``KEYS``. A mix that
# asks for anything else is refused rather than run as something it is not.
MIX_KEYS = {"name", "source", "why", "driver"}
DEFAULT_DRIVER = "ps_closed_loop"
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def load_json(path: str):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: str = ROOT
    readers: Dict[str, Callable] = field(default_factory=dict)
    driver: Optional[ModuleType] = None

    def metrics(self, trace: bool) -> List[dict]:
        return self.per_layer if trace else self.end_to_end


def _load_module(kind: str, name: str, root: str) -> ModuleType:
    """``benchmark/<kind>/<name>.py``, run as a module of its own."""
    path = os.path.join(root, "benchmark", kind, name + ".py")
    modname = f"benchmark_{kind}_" + re.sub(r"[.-]", "_", name)
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_driver(name, root: str = ROOT) -> ModuleType:
    """``benchmark/drivers/<name>.py``; a name that is no such file is
    refused."""
    if not (isinstance(name, str) and NAME.fullmatch(name) and os.path.isfile(
            os.path.join(root, "benchmark", "drivers", name + ".py"))):
        raise ValueError(f"no driver {name!r} in benchmark/drivers/")
    return _load_module("drivers", name, root)


def check_traffic(mix: dict, root: str = ROOT) -> ModuleType:
    """The driver a mix names, once it has accepted the mix."""
    driver = load_driver(mix.get("driver", DEFAULT_DRIVER), root)
    unknown = set(mix) - MIX_KEYS - set(driver.KEYS)
    if unknown:
        raise ValueError(f"traffic {mix.get('name')!r}: unknown keys "
                         f"{sorted(unknown)}")
    driver.check_mix(mix)
    return driver


def load_reader(name: str, root: str = ROOT) -> Callable:
    """The ``read`` function of ``benchmark/metrics/<name>.py``."""
    return _load_module("metrics", name, root).read


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(root, "benchmark", "traffic",
                                     w["traffic"] + ".json"))
    driver = check_traffic(traffic, root)
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if (name in m["workloads"] if "workloads" in m
               else m["moves"] in e2e_names)]
    cell = Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per, root=root,
                driver=driver)
    cell.readers = {m["name"]: load_reader(m["name"], root)
                    for m in e2e + per}
    return cell


def peaks(device_kind: str, root: str = ROOT) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = load_json(os.path.join(root, "benchmark", "peaks.json"))
    if device_kind not in table["kinds"]:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/peaks.json ({sorted(table['kinds'])})")
    return table["kinds"][device_kind]


def tensors(config: dict, tiny: Optional[int] = None) -> List[tuple]:
    """[(name, shape)] as the configuration lists them; ``tiny`` caps every
    dimension (the CPU rehearsal's size, never a measured one)."""
    out = []
    for name, shape in config["tensors"]:
        shape = tuple(int(d) for d in shape)
        if tiny:
            shape = tuple(min(d, tiny) for d in shape)
        out.append((name, shape))
    return out
