"""A cell whose configuration names a family module that the benchmark
finds by name from files alone: a copy of the dense family under another
name, in a bench directory of its own. The tiny cell is built, run and
checked on the CPU through the copy, and the check's readings of that
one window through the copy and through the dense family are the same
bits."""
import json
import os
import shutil
import time

from bench import correct, harness, spec, traffic
from bench.families import dense

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _bench_dir(tmp_path):
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "families"):
        (bench / d).mkdir(parents=True)
    shutil.copy(os.path.join(spec.BENCH_DIR, "families", "dense.py"),
                bench / "families" / "dense_copy.py")
    with open(os.path.join(DATA, "tiny.json")) as f:
        cfg = json.load(f)
    cfg["family"] = "dense_copy"
    (bench / "configs" / "tiny.json").write_text(json.dumps(cfg))
    shutil.copy(os.path.join(DATA, "tiny_mix.json"), bench / "traffic")
    doc = {"workloads": [{"name": "tiny.copy", "config": "tiny",
                          "traffic": "tiny_mix", "chips": 1}],
           "end_to_end": [], "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    return str(bench)


def test_cell_runs_through_a_family_found_by_name(tmp_path):
    bench = _bench_dir(tmp_path)
    cell = spec.load_cell("tiny.copy", root=str(tmp_path), bench_dir=bench)
    assert cell.family.__file__ == os.path.join(bench, "families",
                                                "dense_copy.py")
    assert cell.family.make_expert is not dense.make_expert
    compiles = harness.CompileLog()
    sys_ = harness.build(cell, 6, time.perf_counter())
    assert type(sys_.arch) is cell.family.Arch
    harness.warm(sys_)
    offers = traffic.offered(cell.traffic, 6, 2.0, sys_.arch.vocab,
                             sys_.clients)
    win = harness.run_window(sys_, offers, 2.0, compiles)
    got = correct.collect(sys_, win)
    correct.free(sys_)
    through_copy = correct.readings(sys_, got)
    ok, table = correct.verdict(cell.config, through_copy)
    assert ok, table
    assert through_copy["checked_tokens"] > 0
    sys_.family, sys_.arch = dense, dense.Arch.from_config(cell.config)
    assert correct.readings(sys_, got) == through_copy
