"""Find a cell's files by name and check them.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``chipbench/configs/<config>.json``) under a traffic mix
(``chipbench/traffic/<traffic>.json``).  A configuration's model head is
``chipbench/heads/<arch>.py``; per-layer metrics are readers in
``chipbench/metrics/<name>.py``.  Nothing here knows any cell, head or
metric by name, so a later one is a new file and an entry in
``BENCHMARK.json``.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import numbers
import pathlib
import re

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

# Every head's keys; a head names its own in ``KEYS``.
CONFIG_KEYS = ("name", "source", "arch", "embed_dim", "n_dense", "row_cut",
               "row_cut_above", "tables", "serve", "correctness", "reduced",
               "assumed")
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


def _load_module(path: pathlib.Path, name: str):
    if not path.is_file():
        return None
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        return None
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def load_head(arch: str, root: pathlib.Path = ROOT):
    """The module ``<root>/chipbench/heads/<arch>.py``: its ``KEYS``,
    ``check``, ``program_config``, ``make_weights``, ``reference_scores``,
    ``dense_flops_per_request`` and ``top_in``.  Loaded once per process,
    so its compiled functions are too."""
    if not isinstance(arch, str) or not re.fullmatch(r"[A-Za-z0-9_]+", arch):
        raise SpecError(f"arch {arch!r} is not a head's name")
    path = pathlib.Path(root) / "chipbench" / "heads" / f"{arch}.py"
    mod = _load_module(path, f"chipbench_head_{arch}")
    if mod is None:
        raise SpecError(f"arch {arch!r} has no head in chipbench/heads")
    return mod


def load_config(path: pathlib.Path, root: pathlib.Path = ROOT) -> dict:
    """A configuration file, checked: every key present, its own head's
    too, the table list matching the stated row cut (``1/row_cut`` of each
    table over ``row_cut_above`` declared rows, the others whole),
    ``reduced`` naming exactly the tables cut, and the head's own rules."""
    cfg = json.loads(pathlib.Path(path).read_text())
    missing = [k for k in CONFIG_KEYS if k not in cfg]
    if missing:
        raise SpecError(f"{path}: missing {missing}")
    try:
        head = load_head(cfg["arch"], pathlib.Path(root))
    except SpecError as exc:
        raise SpecError(f"{path}: {exc}") from None
    missing = [k for k in head.KEYS if k not in cfg]
    if missing:
        raise SpecError(f"{path}: missing {missing}, keys of head "
                        f"{cfg['arch']!r}")
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
    try:
        head.check(cfg)
    except SpecError as exc:
        raise SpecError(f"{path}: {exc}") from None
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
    root = pathlib.Path(root)
    cfg = load_config(root / configs[w["config"]]["file"], root)
    mix = load_traffic(root / "chipbench" / "traffic" / f"{w['traffic']}.json")

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
    mod = _load_module(path, f"chipbench_metric_{metric.replace('.', '_')}")
    if mod is None:
        raise SpecError(f"no reader for metric {metric!r} at {path}")
    return mod.read
