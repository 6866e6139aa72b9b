"""The benchmark's data, found by name: ``BENCHMARK.json`` at the
checkout's root, one file per configuration (``configs/<name>.json``),
per traffic mix (``mixes/<name>.json``) and per per-layer metric reader
(``metrics/<name>.py``), and the table of device peaks
(``peaks.json``).  Adding a cell, a mix or a metric adds files and
entries; nothing here is edited for it.

Names are checked before anything runs: a name starts with a letter, a
digit or ``_`` and has at most 64 letters, digits, ``_``, ``.`` and
``-``; a unit has 1 to 16 letters, digits, ``_``, ``/``, ``%``, ``.``
and ``-``.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

NAME_RE = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT_RE = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


class SpecError(ValueError):
    """The benchmark's data names something that is not there, or a
    name or unit breaks the rules above."""


def check_name(name: str, what: str = "name") -> str:
    if not isinstance(name, str) or not NAME_RE.fullmatch(name):
        raise SpecError(f"bad {what} {name!r}: want {NAME_RE.pattern}")
    return name


def check_unit(unit: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.fullmatch(unit):
        raise SpecError(f"bad unit {unit!r}: want {UNIT_RE.pattern}")
    return unit


def load_json(path: Path) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


class Spec:
    """``BENCHMARK.json`` plus the directory its named files live in
    (``base``; the tests point it at a temporary copy)."""

    def __init__(self, bench: dict, base: Path = HERE):
        self.bench = bench
        self.base = Path(base)
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            for entry in bench.get(key, ()):
                check_name(entry["name"], key)
                if "unit" in entry:
                    check_unit(entry["unit"])
                if entry.get("better", "lower") not in ("lower", "higher"):
                    raise SpecError(f"{entry['name']}: better is "
                                    f"{entry['better']!r}")
        for w in bench.get("workloads", ()):
            check_name(w["config"], "config")
            check_name(w["traffic"], "traffic")

    @classmethod
    def load(cls, root: Path = ROOT, base: Path = HERE) -> "Spec":
        path = Path(root) / "BENCHMARK.json"
        if not path.exists():
            raise SpecError(f"no {path}")
        return cls(load_json(path), base)

    # -- lookups by name -------------------------------------------------
    def _entry(self, key: str, name: str) -> dict:
        check_name(name, key)
        for entry in self.bench.get(key, ()):
            if entry["name"] == name:
                return entry
        raise SpecError(f"unknown {key} entry {name!r}")

    def workload(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        """The configuration's file: the preset it runs and what it
        changes, assumes and guarantees."""
        self._entry("configs", name)
        path = self.base / "configs" / f"{name}.json"
        if not path.exists():
            raise SpecError(f"configuration {name!r} has no file")
        return load_json(path)

    def mix(self, name: str) -> dict:
        check_name(name, "traffic")
        path = self.base / "mixes" / f"{name}.json"
        if not path.exists():
            raise SpecError(f"unknown traffic mix {name!r}")
        return load_json(path)

    def end_to_end(self, workload: str) -> list[dict]:
        return [m for m in self.bench.get("end_to_end", ())
                if workload in m.get("workloads", (workload,))]

    def per_layer(self, workload: str) -> list[dict]:
        return [m for m in self.bench.get("per_layer", ())
                if workload in m.get("workloads", (workload,))]


def reader(name: str, base: Path = HERE):
    """The reader of per-layer metric ``name``: ``metrics/<name>.py``,
    else ``metrics/<stem>.py`` for a name ``<stem>.<mix>`` whose reader
    serves every mix.  Its ``read(run)`` returns the number or None."""
    check_name(name, "metric")
    for stem in (name, name.split(".", 1)[0]):
        path = Path(base) / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"benchmark_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise SpecError(f"no reader for per-layer metric {name!r}")


def peaks(device_kind: str, base: Path = HERE) -> dict:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    table = load_json(Path(base) / "peaks.json")
    row = table["devices"].get(device_kind)
    if row is None:
        raise SpecError(f"no peaks for device kind {device_kind!r}; "
                        f"the table has {sorted(table['devices'])}")
    return row
