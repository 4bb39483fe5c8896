"""Name → files: how the harness finds what belongs to a cell.

``BENCHMARK.json`` names the cell's configuration and traffic mix and the
metrics; the rest is found by name under ``benchmark/``:

    configs/<config>.json        sizes, job (optimizer, precision), checks
    traffic/<traffic>.json       ``kind`` + the mix's parameters
    workloads/<cell>.json        the cell's ``layout``
    layouts/<layout>.json        chips, mesh axes, ZeRO stage
    families/<family>.py         model, batches, reference, FLOPs
    traffic_kinds/<kind>.py      the loop that drives the system
    metrics/<metric>.py          one per-layer metric's reader
    peaks.json                   published peaks by ``device_kind``

Nothing is registered in a table: a later PR adds files and ``BENCHMARK.json``
entries and edits none.  ``root`` is the checkout (default: the one this
file lives in), so a test can point the loader at a copy with more files.
"""

import dataclasses
import importlib.util
import json
import os
import re
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the contract's rule for every name in BENCHMARK.json
NAME = re.compile(r"[A-Za-z0-9][A-Za-z0-9_.-]{0,63}\Z")


class CellError(ValueError):
    """A name that resolves to no file, or files that disagree."""


def read_json(root, *parts):
    path = os.path.join(root, *parts)
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise CellError(f"{os.path.relpath(path, root)} does not exist") \
            from None


def manifest(root=ROOT):
    return read_json(root, "BENCHMARK.json")


def plugin(root, group, name):
    """The module ``benchmark/<group>/<name>.py`` of the checkout at
    ``root``, loaded by path (so a copy of the tree with one more file needs
    no registration, and no entry in ``sys.modules`` to clash)."""
    if not NAME.match(name) or not name.isidentifier():
        raise CellError(f"{group} name {name!r} is not a module name")
    path = os.path.join(root, "benchmark", group, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{group}.{name}", path)
    if spec is None or not os.path.exists(path):
        raise CellError(f"benchmark/{group}/{name}.py does not exist")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def peaks(device_kind, root=ROOT):
    """The published peaks of the chip that reports ``device_kind``; a chip
    with no row is an error, never a default."""
    table = read_json(root, "benchmark", "peaks.json")
    if device_kind not in table:
        raise CellError(f"device kind {device_kind!r} is not in "
                        f"benchmark/peaks.json (known: {sorted(table)})")
    return table[device_kind]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    why: str
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    layout: dict
    family: types.ModuleType
    kind: types.ModuleType
    #: the BENCHMARK.json metric entries this cell reports
    end_to_end: list
    per_layer: list
    root: str = ROOT


def _for_cell(entries, name):
    return [m for m in entries
            if "workloads" not in m or name in m["workloads"]]


def load(name, root=ROOT):
    """Resolve the cell ``name`` of ``root``'s BENCHMARK.json to its files."""
    man = manifest(root)
    entry = next((w for w in man["workloads"] if w["name"] == name), None)
    if entry is None:
        raise CellError(f"no workload {name!r} in BENCHMARK.json (has: "
                        f"{[w['name'] for w in man['workloads']]})")
    conf_entry = next((c for c in man["configs"]
                       if c["name"] == entry["config"]), None)
    if conf_entry is None:
        raise CellError(f"workload {name!r} names the configuration "
                        f"{entry['config']!r}, which BENCHMARK.json lacks")
    config = read_json(root, conf_entry["file"])
    traffic = read_json(root, "benchmark", "traffic",
                        entry["traffic"] + ".json")
    layout_name = read_json(root, "benchmark", "workloads",
                            name + ".json")["layout"]
    layout = read_json(root, "benchmark", "layouts", layout_name + ".json")
    layout["name"] = layout_name
    if layout["chips"] != entry["chips"]:
        raise CellError(f"workload {name!r} asks for {entry['chips']} chips "
                        f"but its layout {layout_name!r} is for "
                        f"{layout['chips']}")
    return Cell(
        name=name, chips=entry["chips"], why=entry["why"],
        config_name=entry["config"], traffic_name=entry["traffic"],
        config=config, traffic=traffic, layout=layout,
        family=plugin(root, "families", config["family"]),
        kind=plugin(root, "traffic_kinds", traffic["kind"]),
        end_to_end=_for_cell(man["end_to_end"], name),
        per_layer=_for_cell(man["per_layer"], name), root=root)
