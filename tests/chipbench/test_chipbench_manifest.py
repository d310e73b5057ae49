"""BENCHMARK.json and the tiny manifest against the contract's limits."""

import copy
import json
import os

import pytest

from chipbench import manifest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny")


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("root", [REPO, TINY], ids=["BENCHMARK.json", "tiny"])
def test_manifest_is_valid_and_every_part_is_found(root):
    parts = manifest.load(root)
    for cell in parts.doc["workloads"]:
        config = parts.config(cell)
        parts.data("traffic", cell["traffic"])
        parts.module("kinds", config["kind"])
        parts.module("references", config["reference"])
        for section in ("end_to_end", "per_layer"):
            for m in parts.metrics(section, cell):
                assert callable(parts.module("metrics", m["name"]).read)
        assert set(config["limits"]), "a configuration states its limits"


def test_cells_are_the_issues_three_in_order(doc):
    assert [(w["name"], w["chips"]) for w in doc["workloads"]] == [
        ("kmeans-fit-1chip", 1), ("cdist-susy-1chip", 1), ("kmeans-fit-4chip", 4),
    ]
    assert doc["paths"] == ["chipbench", "tests/chipbench"]


def test_configurations_keep_the_sources_shapes():
    parts = manifest.load(REPO)
    km = parts.config(parts.cell("kmeans-fit-1chip"))
    km4 = parts.config(parts.cell("kmeans-fit-4chip"))
    cd = parts.config(parts.cell("cdist-susy-1chip"))
    assert (km["n_clusters"], km["max_iter"]) == (8, 30) and km["tol"] < 0
    assert (km4["n_clusters"], km4["max_iter"], km4["features"]) == (8, 30, km["features"])
    assert km4["rows"] == 4 * km["rows"] and km["rows"] in (2**24, 2**25)
    assert (cd["rows"], cd["features"], cd["reduced"]) == (40000, 18, {})
    for entry in parts.doc["configs"]:
        with open(os.path.join(REPO, entry["file"])) as f:
            assert sorted(json.load(f)["reduced"]) == sorted(entry["reduced"])


def _break(doc, how):
    doc = copy.deepcopy(doc)
    how(doc)
    return doc


BROKEN = {
    "extra top-level key": lambda d: d.update(note="x"),
    "name with a space": lambda d: d["workloads"][0].update(name="kmeans fit"),
    "unit over 16 characters": lambda d: d["end_to_end"][0].update(unit="milliseconds/call"),
    "unit with a space": lambda d: d["end_to_end"][0].update(unit="items per s"),
    "bound over a tenth": lambda d: d["end_to_end"][0].update(bound=0.2),
    "no setup_s": lambda d: d["end_to_end"].pop(),
    "moves names nothing": lambda d: d["per_layer"][0].update(moves="wall"),
    "moves a metric its cell lacks": lambda d: d["per_layer"][0].update(moves="call_p95_ms"),
    "a why on a metric": lambda d: d["per_layer"][0].update(why="because"),
    "two four-chip cells in three": lambda d: d["workloads"][1].update(chips=4),
    "pair of config and traffic twice": lambda d: d["workloads"][2].update(config="heat-kmeans"),
    "config file outside paths": lambda d: d["configs"][0].update(file="benchmarks/kmeans/config.json"),
    "two metrics of one name": lambda d: d["per_layer"][1].update(name="device_idle_share"),
    "command leaves the repo": lambda d: d["command"].append("../x"),
    "run_seconds over 51": lambda d: d.update(run_seconds=60),
    "unknown source": lambda d: d["per_layer"][0].update(source="guess"),
    "end-to-end from the program": lambda d: d["end_to_end"][0].update(source="program_span"),
    "unused configuration": lambda d: d["workloads"].pop(),
}


@pytest.mark.parametrize("case", sorted(BROKEN))
def test_validation_refuses(doc, case):
    with pytest.raises(manifest.ManifestError):
        manifest.validate(_break(doc, BROKEN[case]))


def test_parts_prefer_the_manifests_own_files_and_fall_back_to_the_harness():
    tiny = manifest.load(TINY)
    assert tiny.table("peaks").keys() == {"cpu"}
    assert manifest.load(REPO).table("peaks").keys() == {"TPU v5 lite"}
    assert tiny.data("traffic", "closed-1")["clients"] == 1  # the harness's file
    with pytest.raises(manifest.ManifestError):
        tiny.data("traffic", "no-such-mix")
    with pytest.raises(manifest.ManifestError):
        tiny.module("kinds", "../run")
