"""Every name in BENCHMARK.json is found as a file of its own."""
import json

import pytest

from chipbench import families, spec

BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
PER_LAYER = BENCH["per_layer"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_its_files(cell):
    c = spec.load_cell(cell)
    assert c.config["name"] in {x["name"] for x in BENCH["configs"]}
    assert c.traffic["rate_rps"] > 0
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:           # what a metric moves is reported there
        assert m["moves"] in names


@pytest.mark.parametrize("metric", PER_LAYER, ids=lambda m: m["name"])
def test_per_layer_reader_matches_entry(metric):
    mod = spec.load_reader(metric["name"])
    assert callable(mod.read)
    assert mod.LAYER == metric["layer"]
    assert mod.SOURCE == metric["source"]
    assert mod.MOVES == metric["moves"]


@pytest.mark.parametrize("metric", BENCH["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_reader_exists(metric):
    mod = spec.load_reader(metric["name"])
    assert mod.SOURCE == metric["source"] == "host_clock"


def test_configs_hold_what_is_run():
    for c in BENCH["configs"]:
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert {"num_blocks", "block_size", "max_ctx"} <= set(cfg["serve"])
        assert set(cfg["check"]["limits"]) == {"gap_mean"}
        assert (families.HERE / f"{cfg['model_type']}.py").exists()


def test_unknown_names_are_errors(tmp_path):
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.load_reader("no_such_metric")
