import json
import os

import catalog
import pytest

BENCH = catalog.benchmark()


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_is_found_by_name_with_all_its_parts(cell):
    c = catalog.find_cell(cell)
    assert c.chips in (1, 4)
    assert c.config["thresholds"]
    gen = catalog.generator(c.traffic["kind"])
    assert gen.services(c.traffic)
    for part in c.traffic.get("parts", [c.traffic]):
        catalog.generator(part["kind"])
        assert part["cls"] in c.config["thresholds"]
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(catalog.reader(m["name"]).read)


def test_each_per_layer_metric_moves_an_end_to_end_metric_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert "workloads" not in moved or cell in moved["workloads"], (m["name"], cell)


def test_config_files_exist_and_are_distinct():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        cfg = catalog.load_json(os.path.join(catalog.ROOT, c["file"]))
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) <= set(cfg.get("reduced", {}))


def test_unknown_names_are_errors():
    with pytest.raises(catalog.CatalogError):
        catalog.find_cell("no-such-cell")
    with pytest.raises(catalog.CatalogError):
        catalog.reader("no_such_metric")
    with pytest.raises(catalog.CatalogError):
        catalog.generator("no_such_kind")


def test_a_dotted_metric_falls_back_to_its_quantity_reader():
    assert catalog.reader("device_idle_share.rate").__file__.endswith("device_idle_share.py")
