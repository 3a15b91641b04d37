"""BENCHMARK.json and the files it names: every cell, mix, metric
reader and configuration is found by name, and a new mix is a new file."""
import json
import re
import shutil

import pytest

from perfbench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_every_cell_resolves_with_its_metrics(bench):
    for w in bench["workloads"]:
        cell = spec.resolve(bench, w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert hasattr(spec.reader(m["name"]), "read")
        assert cell.config["name"] == w["config"]


def test_names_units_and_bounds(bench):
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["layer"] for m in bench["per_layer"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and m["layer"] in layers
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and c["file"].startswith("perfbench/")
    texts = ([x["why"] for x in bench["configs"] + bench["workloads"]]
             + [c["source"] for c in bench["configs"]]
             + [m["layer"] for m in bench["per_layer"]])
    assert all(1 <= len(t) <= 200 and "\n" not in t for t in texts)


def test_a_new_mix_is_found_by_name(tmp_path, bench):
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    mix = dict(spec.load_mix("chat"), rate_per_s=0.5, slots=4)
    (root / "perfbench" / "mixes" / "chat-slow.json").write_text(
        json.dumps(mix))
    b = json.loads(json.dumps(bench))
    b["workloads"].append({"name": "deepseek-7b.q8_0.chat-slow",
                           "config": "deepseek-7b.q8_0",
                           "traffic": "chat-slow", "chips": 1,
                           "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.resolve(spec.load_benchmark(root),
                        "deepseek-7b.q8_0.chat-slow", root)
    assert cell.mix["rate_per_s"] == 0.5 and cell.mix["slots"] == 4
    # metrics that list their cells leave the new one out until listed
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s", "itl_p95_ms", "setup_s"}


def test_unknown_cell_is_an_error(bench):
    with pytest.raises(KeyError):
        spec.resolve(bench, "no-such-cell")


def test_read_metrics_leaves_out_what_finds_nothing(bench):
    from types import SimpleNamespace
    obs = SimpleNamespace(trace=None, setup_s=3.0)
    entries = [m for m in bench["end_to_end"] + bench["per_layer"]
               if m["name"] in ("setup_s", "device_idle_share")]
    assert spec.read_metrics(entries, obs) == {
        "setup_s": {"value": 3.0, "unit": "s"}}
