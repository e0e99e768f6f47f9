"""The entry point and BENCHMARK.json against the benchmark contract."""
import json
import re

from bench import harness, run
from bench.tests.conftest import BENCH

SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_no_tpu_exits_non_zero_with_no_result_line(capsys):
    rc = run.main(["--workload", "han_imdb.full", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert "{" not in out.out
    assert "no TPU" in out.err


def test_unknown_workload_exits_non_zero(capsys):
    argv = ["--workload", "nope", "--seed", "1", "--seconds", "1"]
    assert run.main(argv) != 0
    assert capsys.readouterr().out == ""


def test_benchmark_json_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in SPEC["configs"]]
             + [w["name"] for w in SPEC["workloads"]]
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in SPEC["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] == 1
    assert [w["name"] for w in SPEC["workloads"]] == [
        "han_imdb.full", "han_imdb.serve_zipf", "rgcn_imdb.full",
        "rgcn_imdb.serve_sat"]


def test_every_cell_finds_its_files_and_reports_enough():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for w in SPEC["workloads"]:
        cell = harness.find_cell(w["name"])
        reported = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell["per_layer"]
        for m in cell["per_layer"]:
            assert m["moves"] in reported and m["moves"] in e2e
            assert callable(harness.reader(m["name"]))
        for key in ("use_pallas", "degree_buckets", "fuse_na_sa",
                    "overlap", "cache_rows", "partitions"):
            assert key not in cell["config"]
        assert cell["limits"]["logit_rel_err"] > 0
