"""The four-chip cell's configuration: the paper's multi-processor layout
of the cit-Patents graph.  It runs what the tests' four-chip batch cell
(``testkit.x4_cell``, built on ``configs/cit-patents-16-x4.json``) runs,
so those tests cover the cell of ``BENCHMARK.json``."""

import json
from pathlib import Path

import pytest

from chip import run
from chip.testkit import X4

HERE = Path(run.__file__).parent
#: the keys in which the two files may differ: what names the deployment
NAMING = {"name", "source", "graph_source", "deployment"}


def _config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def test_the_four_chip_cell_runs_the_tested_configuration():
    cell = run.resolve(run.load_benchmark(), X4)
    tested = _config("cit-patents-16-x4")
    assert cell["chips"] == 4
    assert {k: v for k, v in cell["config"].items() if k not in NAMING} == {
        k: v for k, v in tested.items() if k not in NAMING}
    assert cell["config"]["engine"]["partition"] is True


@pytest.mark.parametrize("key", ["n", "arcs", "exponent", "structure_seed",
                                 "source_graph", "realized", "reduced"])
def test_the_four_chip_cell_counts_the_one_chip_graph(key):
    assert _config("cit-patents-16-mp4")[key] == _config("cit-patents-16")[key]


def test_each_configuration_names_its_own_source():
    bench = run.load_benchmark()
    specs = [(c["source"], tuple(c["reduced"])) for c in bench["configs"]]
    assert len(specs) == len(set(specs))
    for spec in bench["configs"]:
        config = json.loads((HERE.parents[1] / spec["file"]).read_text())
        assert (config["name"], config["source"]) == (spec["name"],
                                                      spec["source"])
