"""`BENCHMARK.json` and the files it names, found by name.

A cell names a configuration and a traffic mix; each metric names a reader.
Each is a file of its own, so a later change adds a cell, a configuration, a
mix or a metric by adding files and entries, never by editing one:

  configs:  the `file` that the configuration's entry gives
            (`chipbench/configs/<config>.json`)
  traffic:  `chipbench/traffic/<mix>.json`
  metrics:  `chipbench/metrics/<metric>.py`, a module with `read(run)`
  reference: `chipbench/references/<name>.py`, named by the configuration
            file's optional key `"reference": "<name>"`; without the key,
            `decoder_moe` (DEFAULT_REFERENCE)

A reference module holds all that is particular to one model architecture.
It imports nothing of the program and provides, with `m` the configuration
file's `model` block and `w` the weights it made:

  weights(m, seed)             the benchmark's weights from the seed, in the
                               program's parameter layout and served dtype
  hidden_states(m, w, tokens, control=False)
                               float32 final hidden states [n, d] of one
                               prompt, what the head reads; with `control`,
                               computed in the precision below the served one
  head_logits(m, w, h, control=False)
                               float32 logits [V] of one hidden state [d]
  prompt_flops(m, n)           model FLOPs this chip does for a prompt of n
                               tokens
  routed_rows(m, w, tokens)    real (token, expert) rows that the experts
                               held on this chip serve for one prompt,
                               counted from the module's own float32
                               routing, never from the program's counters

Each of these refuses, with a ValueError naming the key, a `model` key that
the module does not compute, or a value at which it does not compute it.
Every `model` key is also compared with the program's configuration
(`system.program_config`), so what the reference computes is what the
program runs.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import types
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NAME_RE = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT_RE = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


DEFAULT_REFERENCE = "decoder_moe"


class ManifestError(ValueError):
    """A name, unit or file that the benchmark's contract refuses."""


def check_name(name: Any, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.fullmatch(name):
        raise ManifestError(f"{what} {name!r}: a name is 1-64 of A-Z a-z 0-9 "
                            f"_ . - and starts with a letter, digit or _")
    return name


def check_unit(unit: Any, what: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.fullmatch(unit):
        raise ManifestError(f"{what} unit {unit!r}: a unit is 1-16 of A-Z "
                            f"a-z 0-9 _ / % . -")
    return unit


def _load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> Dict[str, Any]:
    """Read `BENCHMARK.json` and check every name and unit in it."""
    man = _load_json(os.path.join(root, "BENCHMARK.json"))
    for c in man["configs"]:
        check_name(c["name"], "config")
        for k in c["reduced"]:
            check_name(k, f"config {c['name']} reduced key")
    for w in man["workloads"]:
        check_name(w["name"], "workload")
        check_name(w["config"], f"workload {w['name']} config")
        check_name(w["traffic"], f"workload {w['name']} traffic")
    for m in man["end_to_end"] + man["per_layer"]:
        check_name(m["name"], "metric")
        check_unit(m["unit"], f"metric {m['name']}")
        if m["better"] not in ("lower", "higher"):
            raise ManifestError(f"metric {m['name']}: better is lower or "
                                f"higher, not {m['better']!r}")
    return man


@dataclasses.dataclass
class Cell:
    """One workload of the manifest with its files loaded."""
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: str
    reference: types.ModuleType  # the configuration's reference module

    def metrics(self, trace: bool) -> List[Dict[str, Any]]:
        """The metrics a run reports: end to end with --trace 0, per layer
        with --trace 1."""
        return self.per_layer if trace else self.end_to_end


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_config(man: Dict[str, Any], name: str, root: str = ROOT
                ) -> Dict[str, Any]:
    entry = [c for c in man["configs"] if c["name"] == name]
    if not entry:
        raise ManifestError(f"no configuration named {name!r}")
    return _load_json(os.path.join(root, entry[0]["file"]))


def load_traffic(name: str, root: str = ROOT) -> Dict[str, Any]:
    path = os.path.join(root, "chipbench", "traffic",
                        check_name(name, "traffic") + ".json")
    if not os.path.exists(path):
        raise ManifestError(f"no traffic mix {name!r} ({path})")
    return _load_json(path)


def load_cell(name: str, root: str = ROOT) -> Cell:
    man = load_manifest(root)
    entry = [w for w in man["workloads"] if w["name"] == name]
    if not entry:
        raise ManifestError(f"no workload named {name!r} in BENCHMARK.json")
    w = entry[0]
    config = load_config(man, w["config"], root)
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config,
                traffic_name=w["traffic"],
                traffic=load_traffic(w["traffic"], root),
                end_to_end=[m for m in man["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in man["per_layer"] if _applies(m, name)],
                root=root, reference=reference_for(config, root))


def _load_module(kind: str, path: str, name: str) -> types.ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, root: str = ROOT):
    """The `read(run)` function of `chipbench/metrics/<metric>.py`."""
    path = os.path.join(root, "chipbench", "metrics",
                        check_name(metric, "metric") + ".py")
    if not os.path.exists(path):
        raise ManifestError(f"no reader for metric {metric!r} ({path})")
    return _load_module("metric", path, metric).read


# one module object per file, so that its jitted functions compile once
_REFERENCES: Dict[str, types.ModuleType] = {}


def load_reference(name: str, root: str = ROOT) -> types.ModuleType:
    """The reference module `chipbench/references/<name>.py`."""
    path = os.path.join(root, "chipbench", "references",
                        check_name(name, "reference") + ".py")
    if not os.path.exists(path):
        raise ManifestError(f"no reference module {name!r} ({path})")
    if path not in _REFERENCES:
        _REFERENCES[path] = _load_module("reference", path, name)
    return _REFERENCES[path]


def reference_for(config: Dict[str, Any], root: str = ROOT
                  ) -> types.ModuleType:
    """The reference module a configuration file names, or the default."""
    return load_reference(config.get("reference", DEFAULT_REFERENCE), root)
