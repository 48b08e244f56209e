"""Find a cell's configuration, traffic mix and metrics by name.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Its files:

- ``configs/<config>.json``: the model as it is run (zoo name, overrides,
  engine settings, the limits of the ``correct`` comparison, the cut that
  the CPU tests run under ``smoke``);
- ``configs/<config>.py``: what the harness computes of that architecture,
  a function ``arch(config)`` that returns a ``reference.Arch`` (its
  float32 layer, the operations of a token, the profiler's modules and
  their reference);
- ``traffic/<traffic>.json``: the driver (``serve`` or ``profile``) and the
  generator's parameters;
- ``metrics/<metric>.py``: one reader per per-layer metric, a function
  ``read(run)`` that returns a number, or None when the run holds nothing
  to read.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH_DIR.parents[1]


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    workloads: Optional[tuple]
    moves: Optional[str] = None


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: tuple
    per_layer: tuple
    model: Any          # the module ``configs/<config>.py``

    @property
    def driver(self) -> str:
        return self.traffic["driver"]

    @property
    def arch(self):
        """The configuration's ``reference.Arch``, built from
        ``config``."""
        return self.model.arch(self.config)


def _metric(entry: Dict[str, Any]) -> Metric:
    wl = entry.get("workloads")
    return Metric(entry["name"], entry["unit"], entry["better"],
                  entry["source"], tuple(wl) if wl is not None else None,
                  entry.get("moves"))


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as fh:
        return json.load(fh)


def load_benchmark(root: Path = CHECKOUT) -> Dict[str, Any]:
    return load_json(root / "BENCHMARK.json")


def cell_metrics(bench: Dict[str, Any], cell: str):
    """(end_to_end, per_layer) metrics the cell reports.  A metric with a
    ``workloads`` list applies to those cells; without one, an end-to-end
    metric applies everywhere and a per-layer metric wherever the
    end-to-end metric it moves is reported."""
    e2e = [m for m in map(_metric, bench["end_to_end"])
           if m.workloads is None or cell in m.workloads]
    names = {m.name for m in e2e}
    per = [m for m in map(_metric, bench["per_layer"])
           if (cell in m.workloads if m.workloads is not None
               else m.moves in names)]
    return tuple(e2e), tuple(per)


def find_cell(bench: Dict[str, Any], name: str,
              bench_dir: Path = BENCH_DIR) -> Cell:
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {known})")
    w = entries[0]
    e2e, per = cell_metrics(bench, name)
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(bench_dir / "configs"
                                 / f"{w['config']}.json"),
                traffic=load_json(bench_dir / "traffic"
                                  / f"{w['traffic']}.json"),
                end_to_end=e2e, per_layer=per,
                model=config_module(w["config"], bench_dir))


def _load(path: Path, prefix: str, name: str):
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


_CONFIG_MODULES: Dict[Path, Any] = {}


def config_module(name: str, bench_dir: Path = BENCH_DIR):
    """The module ``configs/<name>.py``, loaded once per process, so that
    the ``Arch`` class it defines (a static argument of jitted functions)
    stays one class."""
    path = (bench_dir / "configs" / f"{name}.py").resolve()
    if path not in _CONFIG_MODULES:
        _CONFIG_MODULES[path] = _load(path, "chipbench_config_", name)
    return _CONFIG_MODULES[path]


def metric_reader(name: str, bench_dir: Path = BENCH_DIR
                  ) -> Callable[[Any], Optional[float]]:
    """The ``read`` function of ``metrics/<name>.py``."""
    return _load(bench_dir / "metrics" / f"{name}.py", "chipbench_metric_",
                 name).read


def read_per_layer(metrics: List[Metric], run,
                   bench_dir: Path = BENCH_DIR) -> Dict[str, Dict]:
    """Each per-layer metric's reader applied to the run; a reader that
    finds nothing to read leaves its metric out."""
    out = {}
    for m in metrics:
        value = metric_reader(m.name, bench_dir)(run)
        if value is not None:
            out[m.name] = {"value": value, "unit": m.unit}
    return out


def model_config(config: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file: the zoo entry
    with the file's overrides applied (an object given as a JSON object
    replaces the fields it names)."""
    import dataclasses
    from repro.configs import get_config
    base = get_config(config["zoo"])
    over = {}
    for key, value in config.get("overrides", {}).items():
        current = getattr(base, key)
        over[key] = (dataclasses.replace(current, **value)
                     if isinstance(value, dict)
                     and dataclasses.is_dataclass(current) else value)
    return base.with_overrides(**over)
