"""BENCHMARK.json names only files that exist, in names the harness can
find, and gives every cell what each run has to report."""
import json
import re

import pytest

import traffic
from conftest import BENCH

SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_names_and_files():
    for c in SPEC["configs"]:
        assert NAME.match(c["name"])
        cfg = traffic.load(BENCH.parent / c["file"])
        assert cfg["name"] == c["name"]
        for k in c["reduced"]:
            assert k in cfg["reduced"], k
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
    for m in SPEC["per_layer"]:
        assert NAME.match(m["name"])
        assert (BENCH / "metrics" / f"{m['name']}.py").exists(), m["name"]


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_reports_enough(cell):
    def applies(m):
        return "workloads" not in m or cell in m["workloads"]
    e2e = {m["name"] for m in SPEC["end_to_end"] if applies(m)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in SPEC["per_layer"] if applies(m)]
    assert layer
    assert all(m["moves"] in e2e for m in layer)


@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_every_configuration_resolves_to_an_architecture_file(config):
    import arch
    c = {x["name"]: x for x in SPEC["configs"]}[config]
    cfg = traffic.load(BENCH.parent / c["file"])
    path = BENCH / "arch" / f"{cfg.get('arch', arch.DEFAULT)}.py"
    assert path.is_file()
    assert arch.load(cfg).__file__ == str(path)
