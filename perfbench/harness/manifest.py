"""Load ``BENCHMARK.json`` and resolve a cell to its files, by name.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric sits in a file of its own under the benchmark directory:

    configs/<config>.json        sizes as run, program overrides, source,
                                 optionally ``seeded_weights`` (weights.py)
    configs/<config>.py          plain float32 reference, work counts and
                                 each request's inputs besides its tokens
    traffic/<traffic>.json       the mix's parameters
    cells/<workload>.json        the limits the output check holds the cell to
    layer_metrics/<metric>.py    one reader per per-layer metric

so a later change adds a configuration, mix, cell or metric by adding
files and manifest entries, never by editing the harness.

A configuration's module gives:

    program_fields(c)            the program's model fields it fixes
    request_inputs(c, rng, clients)
                                 {name: array with ``clients`` rows}, drawn
                                 from ``rng`` alone: a batch's own stream of
                                 the seed (``traffic.inputs_rng``). Each name
                                 is a keyword input of
                                 ``repro.launch.serve.generate``, which is
                                 given the batch's arrays; ``{}`` for none
    logits(w, c, extra, prompt, served, *, lower=None)
                                 the reference; ``extra`` is the request's
                                 own row of each input, or None for none
    work(c, batch, prompt, gen)  operations and bytes of one batch

``c`` is the configuration's JSON. The harness draws a batch's inputs
next to its prompts, between batches, where the window counts the host's
time: draw large inputs as uniform floats (``rng.random(shape,
dtype=np.float32)``), which take about a quarter of the time of normals.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

#: the benchmark directory (``perfbench/``) and the checkout holding it
BENCH_DIR = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    """The manifest or a file it names is malformed or missing."""


def _check_name(kind: str, name) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ManifestError(f"bad {kind} name {name!r}")
    return name


def _load_module(path: Path) -> ModuleType:
    if not path.is_file():
        raise ManifestError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(
        "perfbench_" + re.sub(r"\W", "_", path.stem), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise ManifestError(f"missing file {path}")
    return json.loads(path.read_text())


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    workloads: list[str] | None
    layer: str | None = None
    moves: str | None = None
    reader: ModuleType | None = None


@dataclass
class Cell:
    """One workload with everything it names, resolved."""

    name: str
    chips: int
    config_name: str
    config: dict
    reference: ModuleType
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: list[Metric]
    per_layer: list[Metric]


def load(root: Path = CHECKOUT) -> dict:
    """The manifest, with every name and unit checked."""
    man = _load_json(root / "BENCHMARK.json")
    for c in man["configs"]:
        _check_name("config", c["name"])
        for key in c["reduced"]:
            _check_name("reduced key", key)
    for w in man["workloads"]:
        for k in ("name", "config", "traffic"):
            _check_name(k, w[k])
        if w["chips"] not in (1, 4):
            raise ManifestError(f"{w['name']}: chips must be 1 or 4")
    for m in man["end_to_end"] + man["per_layer"]:
        _check_name("metric", m["name"])
        if not UNIT_RE.match(m["unit"]):
            raise ManifestError(f"bad unit {m['unit']!r} of {m['name']}")
        if m["better"] not in ("lower", "higher"):
            raise ManifestError(f"bad 'better' of {m['name']}")
        if m["source"] not in SOURCES:
            raise ManifestError(f"bad source of {m['name']}")
    names = [x["name"] for x in man["end_to_end"] + man["per_layer"]]
    names += [x["name"] for x in man["workloads"]]
    if len(set(names)) != len(names):
        raise ManifestError("duplicate metric or workload name")
    return man


def _metric(entry: dict, root: Path, *, reader: bool) -> Metric:
    m = Metric(
        name=entry["name"], unit=entry["unit"], better=entry["better"],
        source=entry["source"], workloads=entry.get("workloads"),
        layer=entry.get("layer"), moves=entry.get("moves"),
    )
    if reader:
        m.reader = _load_module(root / "perfbench" / "layer_metrics"
                                / f"{m.name}.py")
        if not callable(getattr(m.reader, "read", None)):
            raise ManifestError(f"{m.name}: reader has no read(run)")
    return m


def resolve(workload: str, root: Path = CHECKOUT) -> Cell:
    """The cell named ``workload``, with its configuration, reference,
    traffic, limits and the metrics it reports."""
    man = load(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise ManifestError(f"unknown workload {workload!r}; "
                            f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in man["configs"]}
    if w["config"] not in configs:
        raise ManifestError(f"{workload}: unknown config {w['config']!r}")
    base = root / "perfbench"
    return Cell(
        name=workload,
        chips=w["chips"],
        config_name=w["config"],
        config=_load_json(root / configs[w["config"]]["file"]),
        reference=_load_module(base / "configs" / f"{w['config']}.py"),
        traffic_name=w["traffic"],
        traffic=_load_json(base / "traffic" / f"{w['traffic']}.json"),
        limits=_load_json(base / "cells" / f"{workload}.json"),
        end_to_end=[_metric(m, root, reader=False) for m in man["end_to_end"]
                    if workload in m.get("workloads", [workload])],
        per_layer=[_metric(m, root, reader=True) for m in man["per_layer"]
                   if workload in m.get("workloads", [workload])],
    )
