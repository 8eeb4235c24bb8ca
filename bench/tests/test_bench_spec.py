"""BENCHMARK.json keeps the benchmark contract, and every name in it
resolves to its files; a new cell, mix, configuration, model module or
metric is found by adding files and entries alone."""
import json
import re
import shutil

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51


def test_entries_have_exactly_the_contract_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES


def test_names_and_units(bench):
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_every_name_resolves_to_its_files(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cs = spec.resolve(bench, w["name"])
        assert cs.config["name"] == w["config"]
        assert spec.traffic_file(w["traffic"]).is_file()
        assert {"slots", "max_len", "check_tokens", "check_requests",
                "limits"} <= set(cs.engine)
        assert cs.engine["limits"] and set(cs.engine["limits"]) <= {
            "max_logit_gap", "tokens_off_share"}
        reported = {m["name"] for m in spec.end_to_end(bench, w["name"])}
        assert "setup_s" in reported and len(reported) >= 2
        layers = spec.per_layer(bench, w["name"])
        assert layers
        for m in layers:
            assert m["moves"] in reported
            assert callable(spec.metric_reader(m["name"]))
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
    for c in bench["configs"]:
        path = spec.config_file(bench, c["name"])
        assert path.is_file() and c["file"].startswith("bench/")
        conf = spec.load_json(path)
        assert conf["source"] == c["source"]
        assert set(c["reduced"]) <= set(conf)
        model = spec.model_module(conf)
        for name in ("tree", "model_keys", "forward", "prefill_model_flops",
                     "decode_model_flops", "prefill_gemms", "decode_gemms",
                     "warm_insert"):
            assert callable(getattr(model, name)), (c["name"], name)


def test_new_files_are_found_without_editing(bench, tmp_path, monkeypatch):
    root = tmp_path / "repo"
    shutil.copytree(spec.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    new = json.loads(json.dumps(bench))
    new["configs"].append(dict(bench["configs"][0], name="tiny",
                               file="bench/configs/tiny.json"))
    new["workloads"].append({"name": "tiny.burst", "config": "tiny",
                             "traffic": "burst", "chips": 1, "why": "test"})
    new["per_layer"].append({"name": "tiny_metric", "unit": "ms",
                             "better": "lower", "source": "program_span",
                             "layer": "engine", "moves": "setup_s",
                             "workloads": ["tiny.burst"]})
    conf = spec.load_json(spec.config_file(bench, bench["configs"][0]["name"]))
    (root / "bench/configs/tiny.json").write_text(
        json.dumps(dict(conf, name="tiny", model="tiny_model")))
    (root / "bench/models/tiny_model.py").write_text("LAYERS = 'tiny'\n")
    (root / "bench/traffic/burst.json").write_text(json.dumps(
        spec.load_json(spec.traffic_file("chat"))))
    (root / "bench/cells/tiny.burst.json").write_text(json.dumps(
        {"slots": 2, "max_len": 64, "check_tokens": 10, "check_requests": 2,
         "limits": {"max_logit_gap": 0.1}}))
    (root / "bench/metrics/tiny_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    monkeypatch.setattr(spec, "BENCH_DIR", root / "bench")
    monkeypatch.setattr(spec, "REPO_DIR", root)
    b = spec.benchmark(root / "BENCHMARK.json")
    cs = spec.resolve(b, "tiny.burst")
    assert cs.config["name"] == "tiny" and cs.engine["slots"] == 2
    assert spec.model_module(cs.config).LAYERS == "tiny"
    assert [m["name"] for m in spec.per_layer(b, "tiny.burst")] == [
        "compile_s", "tiny_metric"]
    assert spec.metric_reader("tiny_metric")(None) == 42.0
