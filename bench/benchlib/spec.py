"""Where the benchmark finds what a cell is made of.

Everything that belongs to one configuration, one traffic mix or one metric
lives in a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json`` — the configuration's sizes; its ``arch``
  names the architecture module ``bench/models/<arch>.py``, and its
  ``plan_file`` the committed plan beside it;
* ``bench/traffic/<traffic>.json`` — the traffic mix's parameters, read by
  the one general generator (``benchlib.loadgen``);
* ``bench/metrics/<metric>.json`` — a metric's reader
  (``bench/readers/<reader>.py``) and that reader's parameters.

So a later cell, configuration, traffic mix or metric is added by adding
files and entries, never by editing a file that is there.

An architecture module gives ``layers(cfg)`` (the layer list: ``kind``,
``name``, CHW ``in_shape`` and ``out_shape``, ``k``, ``stride``, ``pad``,
``act``, ``save_as``, ``residual_from``), ``make_params(cfg, seed)`` (per
layer a float32 (w, b), or None) and ``program_ops(cfg, params)`` (the op
list the program builds its model from).  Where its layers hold an op kind
or an activation that the shared reference (``benchlib.reference``) or the
shared work counts (``benchlib.work``) do not know (they raise a
``ValueError`` on one), the module names a module of its own for each, by
file stem under ``bench/models/`` (it may name itself):

* ``REFERENCE = "<stem>"`` — the plain reference.  It imports nothing of the
  program and gives ``calibrate(layers, params, calib, qmax=127)``, the
  activation scales (input first, then every layer's output) that the
  program's ``quantize_model`` takes for the model ``program_ops`` builds;
  ``quantize(layers, params, scales, qmax)``, a dict holding at least
  ``out_scale`` (float) and ``qmax``; and ``int_forward(layers, q, x)``,
  the integer logits (B, classes) of float32 inputs ``x``.  The comparison
  (``benchlib.reference.logit_gap_lsb``) stays the shared one.
* ``WORK = "<stem>"`` — the work counts: ``layer_macs(lyr)``,
  ``weight_bytes(lyr)`` and ``activation_bytes(lyr)`` for every layer of
  the list (each may be left out to keep the shared one), and ``FAMILIES``,
  kernel family name -> layer selector, that a metric file's
  ``params.family`` may name.  A shared family of the same name is found
  first, so ``dwconv`` and ``qgemm`` read the same for every architecture.

:meth:`Benchmark.reference` and :meth:`Benchmark.work` resolve them, and
every caller goes through them; readers get the counts as ``rec.work``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys

BENCH_DIR = "bench"


def load_module(path: pathlib.Path):
    """Import a module from its file (readers and architectures are found
    by name, not imported by the harness's code)."""
    path = pathlib.Path(path)
    name = f"_bench_{path.parent.name}_{path.stem}".replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    reader: str                  # bench/readers/<reader>.py
    params: dict


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


class Benchmark:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: pathlib.Path):
        self.root = pathlib.Path(root)
        self.bench = self.root / BENCH_DIR
        self.doc = json.loads((self.root / "BENCHMARK.json").read_text())

    @property
    def cells(self) -> list[str]:
        return [w["name"] for w in self.doc["workloads"]]

    def _json(self, sub: str, name: str) -> dict:
        return json.loads((self.bench / sub / f"{name}.json").read_text())

    def config(self, name: str) -> dict:
        cfg = self._json("configs", name)
        if cfg.get("name") != name:
            raise ValueError(f"configs/{name}.json names {cfg.get('name')!r}")
        return cfg

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def arch(self, cfg: dict):
        return load_module(self.bench / "models" / f"{cfg['arch']}.py")

    def _own(self, cfg: dict, hook: str):
        """The module the architecture names as ``hook``, or None."""
        stem = getattr(self.arch(cfg), hook, None)
        return (None if stem is None else
                load_module(self.bench / "models" / f"{stem}.py"))

    def reference(self, cfg: dict):
        """The configuration's plain reference: its architecture's
        ``REFERENCE`` module, else ``benchlib.reference``."""
        from . import reference as shared
        return self._own(cfg, "REFERENCE") or shared

    def work(self, cfg: dict):
        """The configuration's work counts (``benchlib.work.Counts``), with
        its architecture's ``WORK`` module, if it names one."""
        from .work import Counts
        return Counts(self._own(cfg, "WORK"))

    def reader(self, name: str):
        return load_module(self.bench / "readers" / f"{name}.py")

    def _metric(self, entry: dict) -> Metric:
        data = self._json("metrics", entry["name"])
        if data.get("unit") != entry["unit"] or (
                "moves" in entry and data.get("moves") != entry["moves"]):
            raise ValueError(f"metrics/{entry['name']}.json disagrees with "
                             "BENCHMARK.json on its unit or what it moves")
        return Metric(name=entry["name"], unit=entry["unit"],
                      reader=data["reader"], params=data.get("params", {}))

    def cell(self, name: str) -> Cell:
        try:
            w = next(w for w in self.doc["workloads"] if w["name"] == name)
        except StopIteration:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {self.cells})") from None
        e2e = tuple(self._metric(m) for m in self.doc["end_to_end"]
                    if m.get("workloads") is None or name in m["workloads"])
        e2e_names = {m.name for m in e2e}
        layer = tuple(
            self._metric(m) for m in self.doc["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e_names))
        return Cell(name=name, chips=int(w["chips"]),
                    config=self.config(w["config"]),
                    traffic=dict(self.traffic(w["traffic"]),
                                 name=w["traffic"]),
                    end_to_end=e2e, per_layer=layer)


def program_model(bench: pathlib.Path, cfg: dict, seed: int, params=None):
    """The program's model (``trace_sequential`` over the architecture's op
    list) with the benchmark's weights: ``params``, or those of ``seed``."""
    from repro.core.reinterpret import trace_sequential
    arch = load_module(pathlib.Path(bench) / "models" / f"{cfg['arch']}.py")
    if params is None:
        params = arch.make_params(cfg, seed)
    return trace_sequential(arch.program_ops(cfg, params),
                            tuple(cfg["input_shape"]))
