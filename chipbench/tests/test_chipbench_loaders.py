"""The loaders refuse a file that breaks its rules."""
import json

import pytest

from chipbench import cells

CONFIG = cells.BENCH_DIR / "configs" / "dlrm-flexemr.json"
MIX = cells.BENCH_DIR / "traffic" / "flexemr-zipf-tail.json"


def _write(tmp_path, obj, name="f.json"):
    p = tmp_path / name
    p.write_text(json.dumps(obj))
    return p


@pytest.mark.parametrize("key", ["source", "reduced", "assumed",
                                 # keys of the config's head (dlrm)
                                 "bottom_mlp", "interaction_itself"])
def test_config_without_provenance_refused(tmp_path, key):
    cfg = json.loads(CONFIG.read_text())
    del cfg[key]
    with pytest.raises(cells.SpecError, match=key):
        cells.load_config(_write(tmp_path, cfg))


@pytest.mark.parametrize("key,value,match", [
    ("arch", "dcnv2", "has no head in chipbench/heads"),
    ("arch", "../heads/dlrm", "not a head's name"),
    ("bottom_mlp", [512, 256, 32], "embed_dim"),
    ("top_mlp", [512, 256, 2], "one score"),
])
def test_config_head_refused(tmp_path, key, value, match):
    """An arch with no head file, and the head's own rules."""
    cfg = json.loads(CONFIG.read_text())
    cfg[key] = value
    with pytest.raises(cells.SpecError, match=match):
        cells.load_config(_write(tmp_path, cfg))


def test_config_rows_must_match_the_cut(tmp_path):
    cfg = json.loads(CONFIG.read_text())
    cfg["tables"][0]["rows"] += 1
    with pytest.raises(cells.SpecError, match="cut"):
        cells.load_config(_write(tmp_path, cfg))
    cfg = json.loads(CONFIG.read_text())
    cfg["row_cut"] = 8
    with pytest.raises(cells.SpecError, match="cut"):
        cells.load_config(_write(tmp_path, cfg))
    cfg = json.loads(CONFIG.read_text())
    cfg["row_cut_above"] = 10_000  # the 1M-row tables would be cut too
    with pytest.raises(cells.SpecError, match="cut"):
        cells.load_config(_write(tmp_path, cfg))


@pytest.mark.parametrize("change", ["drop", "add"])
def test_reduced_names_the_tables_cut(tmp_path, change):
    cfg = json.loads(CONFIG.read_text())
    if change == "drop":
        cfg["reduced"].remove("tables.huge_0")
    else:
        cfg["reduced"].append("tables.small_0")
    with pytest.raises(cells.SpecError, match="reduced"):
        cells.load_config(_write(tmp_path, cfg))


@pytest.mark.parametrize("rate", ["2400", None, True, -5, 0])
def test_traffic_rate_must_be_a_number(tmp_path, rate):
    mix = json.loads(MIX.read_text())
    mix["rate_rps"] = rate
    with pytest.raises(cells.SpecError, match="rate_rps"):
        cells.load_traffic(_write(tmp_path, mix))


@pytest.mark.parametrize("key", ["admission", "cooccur_frac", "knee_rps"])
def test_traffic_unknown_key_refused(tmp_path, key):
    mix = json.loads(MIX.read_text())
    mix[key] = 0.3
    with pytest.raises(cells.SpecError, match=key):
        cells.load_traffic(_write(tmp_path, mix))


def test_traffic_law_needs_its_keys(tmp_path):
    mix = json.loads(MIX.read_text())
    del mix["alpha"]
    with pytest.raises(cells.SpecError, match="alpha"):
        cells.load_traffic(_write(tmp_path, mix))
    mix = json.loads(MIX.read_text())
    mix["key_law"] = "uniform"  # alpha is then a key it does not read
    with pytest.raises(cells.SpecError, match="alpha"):
        cells.load_traffic(_write(tmp_path, mix))


def test_every_cell_resolves():
    bench = cells.load_benchmark()
    names = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell = cells.resolve(w["name"])
        reported = {m.name for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
        assert all(m.moves in reported for m in cell.per_layer)
        for m in cell.per_layer:
            assert callable(cells.load_reader(m.name))
        assert reported <= names


def test_unknown_cell_refused():
    with pytest.raises(cells.SpecError):
        cells.resolve("no-such-cell")
