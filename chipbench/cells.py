"""Find a cell's files by name and check them.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``chipbench/configs/<config>.json``) under a traffic mix
(``chipbench/traffic/<traffic>.json``).  Per-layer metrics are readers in
``chipbench/metrics/<name>.py``.  Nothing here knows any cell by name, so a
later cell, mix or metric is a new file and an entry in ``BENCHMARK.json``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import numbers
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

CONFIG_KEYS = ("name", "source", "arch", "embed_dim", "n_dense", "bottom_mlp",
               "top_mlp", "interaction_itself", "row_cut", "row_cut_above",
               "tables", "serve", "correctness", "reduced", "assumed")
TRAFFIC_KEYS = ("source", "key_law", "arrivals", "rate_rps", "warm_s",
                "tail_s")
KEY_LAWS = {"zipf": ("alpha",), "uniform": ()}  # law -> its own keys


class SpecError(ValueError):
    """A benchmark file that breaks its own rules."""


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    moves: str | None = None  # per-layer only
    layer: str | None = None
    workloads: tuple[str, ...] | None = None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


def _metric(entry: dict) -> Metric:
    w = entry.get("workloads")
    return Metric(entry["name"], entry["unit"], entry["better"],
                  entry["source"], entry.get("moves"), entry.get("layer"),
                  tuple(w) if w is not None else None)


def _whole(v) -> bool:
    return isinstance(v, int) and not isinstance(v, bool)


def load_config(path: pathlib.Path) -> dict:
    """A configuration file, checked: every key present, the table list
    matching the stated row cut (``1/row_cut`` of each table over
    ``row_cut_above`` declared rows, the others whole), ``reduced`` naming
    exactly the tables cut, and only a DLRM head (all this harness
    drives)."""
    cfg = json.loads(pathlib.Path(path).read_text())
    missing = [k for k in CONFIG_KEYS if k not in cfg]
    if missing:
        raise SpecError(f"{path}: missing {missing}")
    if cfg["arch"] != "dlrm":
        raise SpecError(f"{path}: arch {cfg['arch']!r} is not served")
    cut, above = cfg["row_cut"], cfg["row_cut_above"]
    if not _whole(cut) or cut < 1 or not _whole(above) or above < 0:
        raise SpecError(f"{path}: row_cut and row_cut_above must be whole "
                        "numbers, row_cut >= 1")
    cut_tables = set()
    for t in cfg["tables"]:
        want = t["declared_rows"]
        if want > above and cut > 1:
            want //= cut
            cut_tables.add(f"tables.{t['name']}")
        if t["rows"] != want:
            raise SpecError(
                f"{path}: table {t['name']} has {t['rows']} rows, the cut "
                f"1/{cut} above {above} rows of {t['declared_rows']} gives "
                f"{want}")
    if not isinstance(cfg["reduced"], list) or not isinstance(
            cfg["assumed"], dict):
        raise SpecError(f"{path}: reduced is a list, assumed a mapping")
    listed = {k for k in cfg["reduced"] if k.startswith("tables.")}
    if listed != cut_tables:
        raise SpecError(f"{path}: reduced must name the tables the cut "
                        f"changes, {sorted(cut_tables)}, not {sorted(listed)}")
    if cfg["bottom_mlp"][-1] != cfg["embed_dim"]:
        raise SpecError(f"{path}: bottom MLP must end at embed_dim")
    if cfg["top_mlp"][-1] != 1:
        raise SpecError(f"{path}: top MLP must end in one score")
    return cfg


def load_traffic(path: pathlib.Path) -> dict:
    """A traffic-mix file, checked: no key that the generator does not
    read, the rate and every length a number."""
    mix = json.loads(pathlib.Path(path).read_text())
    missing = [k for k in TRAFFIC_KEYS if k not in mix]
    if missing:
        raise SpecError(f"{path}: missing {missing}")
    if mix["key_law"] not in KEY_LAWS:
        raise SpecError(f"{path}: key_law must be one of {sorted(KEY_LAWS)}")
    own = KEY_LAWS[mix["key_law"]]
    unknown = sorted(set(mix) - set(TRAFFIC_KEYS) - set(own))
    if unknown or any(k not in mix for k in own):
        raise SpecError(f"{path}: key law {mix['key_law']!r} takes "
                        f"{list(own)}; unknown keys {unknown}")
    for k in ("rate_rps", "warm_s", "tail_s") + own:
        v = mix[k]
        if not isinstance(v, numbers.Real) or isinstance(v, bool) or v < 0:
            raise SpecError(f"{path}: {k} must be a number >= 0, not {v!r}")
    if mix["rate_rps"] <= 0:
        raise SpecError(f"{path}: rate_rps must be positive")
    if mix["arrivals"] != "poisson":
        raise SpecError(f"{path}: arrivals must be 'poisson'")
    return mix


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())


def resolve(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell called ``name``, with its files loaded and the metrics it
    reports: the end-to-end metrics that list it (or list no cells) and
    the per-layer metrics that list it, or that list no cells and move an
    end-to-end metric it reports."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_config(pathlib.Path(root) / configs[w["config"]]["file"])
    mix = load_traffic(BENCH_DIR / "traffic" / f"{w['traffic']}.json")

    def applies(m: Metric) -> bool:
        return m.workloads is None or name in m.workloads

    e2e = tuple(m for m in map(_metric, bench["end_to_end"]) if applies(m))
    names = {m.name for m in e2e}
    layer = tuple(
        m for m in map(_metric, bench["per_layer"])
        if (name in m.workloads if m.workloads is not None
            else m.moves in names))
    return Cell(name, int(w["chips"]), cfg, mix, e2e, layer)


def load_reader(metric: str):
    """The ``read(window)`` function of ``chipbench/metrics/<metric>.py``."""
    path = BENCH_DIR / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_')}", path)
    if spec is None or spec.loader is None:
        raise SpecError(f"no reader for metric {metric!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
