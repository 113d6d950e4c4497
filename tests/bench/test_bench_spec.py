"""Cells find their files by name: a mix, a configuration, a model
family or a metric reader dropped in next to the others is found without
an edit."""
import dataclasses
import json
import os

import pytest

from bench import spec


def _tree(tmp_path):
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics", "families"):
        (bench / d).mkdir(parents=True)
    (bench / "configs" / "m.json").write_text(
        json.dumps({"name": "m", "family": "new_family"}))
    (bench / "families" / "new_family.py").write_text(
        "import dataclasses\n\n\n"
        "@dataclasses.dataclass(frozen=True)\n"
        "class Arch:\n    layers: int\n")
    (bench / "traffic" / "new_mix.json").write_text(
        json.dumps({"arrivals": {"kind": "poisson", "rate_per_s": 3}}))
    (bench / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return run * 2\n")
    doc = {"workloads": [{"name": "m.new", "config": "m",
                          "traffic": "new_mix", "chips": 1}],
           "end_to_end": [{"name": "tok_s"},
                          {"name": "lat", "workloads": ["other"]}],
           "per_layer": [{"name": "new_metric", "workloads": ["m.new"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    return str(bench)


def test_new_mix_is_found_by_name(tmp_path):
    bench = _tree(tmp_path)
    cell = spec.load_cell("m.new", root=str(tmp_path), bench_dir=bench)
    assert cell.traffic["arrivals"]["rate_per_s"] == 3
    assert cell.config == {"name": "m", "family": "new_family"}
    assert [m["name"] for m in cell.end_to_end] == ["tok_s"]
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    assert spec.metric_reader("new_metric", bench_dir=bench)(21) == 42


def test_new_family_is_found_by_name(tmp_path):
    bench = _tree(tmp_path)
    cell = spec.load_cell("m.new", root=str(tmp_path), bench_dir=bench)
    assert cell.family.Arch(layers=3).layers == 3
    assert cell.family.__file__ == os.path.join(bench, "families",
                                                "new_family.py")
    assert spec.known_families(bench) == ["new_family"]


@pytest.mark.parametrize("config", [{"name": "m", "family": "nope"},
                                    {"name": "m"}])
def test_unknown_family_is_an_error(tmp_path, config):
    bench = _tree(tmp_path)
    (tmp_path / "bench" / "configs" / "m.json").write_text(
        json.dumps(config))
    with pytest.raises(KeyError, match=r"known: \['new_family'\]"):
        spec.load_cell("m.new", root=str(tmp_path), bench_dir=bench)
    with pytest.raises(KeyError, match="nope"):
        spec.family("nope", bench_dir=bench)


def test_unknown_cell_is_an_error(tmp_path):
    bench = _tree(tmp_path)
    with pytest.raises(KeyError):
        spec.load_cell("nope", root=str(tmp_path), bench_dir=bench)


def test_every_cell_of_the_benchmark_loads():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    for w in doc["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert cell.family.__name__ == f"bench_family_{cell.config['family']}"
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))


@pytest.mark.parametrize("name", spec.known_families())
def test_every_family_gives_the_interface(name):
    fam = spec.family(name)
    for attr in ("Arch", "program_arch", "make_expert", "served_gaps",
                 "expert_param_bytes", "weight_bytes_read",
                 "kv_bytes_per_token", "prefill_flops", "decode_flops"):
        assert callable(getattr(fam, attr)), attr
    fields = {f.name for f in dataclasses.fields(fam.Arch)}
    assert {"layers", "hidden", "vocab"} <= fields
    assert fam.Arch.__dataclass_params__.frozen
