"""The benchmark finds every piece by name, and BENCHMARK.json keeps to
its contract."""
import json
import re

import bench_tiny
from bench_tiny import REPO

from bench import harness, traffic

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_every_workload_file_names_a_configuration_and_a_cell():
    cells = {c["name"]: c for c in BENCH["workloads"]}
    files = sorted((REPO / "bench" / "workloads").glob("*.json"))
    assert {f.stem for f in files} == set(cells)
    for f in files:
        mix = json.loads(f.read_text())
        assert (REPO / "bench" / "configs" / f"{mix['config']}.json").exists()
        assert (mix["config"], mix["traffic"]) == (
            cells[f.stem]["config"], cells[f.stem]["traffic"])


def test_every_metric_has_a_reader_and_every_family_its_modules():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(harness.load_reader(m["name"])), m["name"]
    for c in BENCH["configs"]:
        conf = json.loads((REPO / c["file"]).read_text())
        assert conf["name"] == c["name"]
        for kind in ("flops", "reference"):
            assert harness.family(kind, conf) is not None


def test_benchmark_json_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    configs = {c["name"] for c in BENCH["configs"]}
    for c in BENCH["workloads"]:
        assert NAME.match(c["name"]) and c["config"] in configs
        assert c["chips"] in (1, 4) and len(c["why"]) <= 200
        spec = harness.load_spec(c["name"])
        assert spec.per_layer and len(spec.end_to_end) >= 2


def test_a_workload_added_as_a_file_is_found_without_code(tmp_path):
    root = bench_tiny.make_root(tmp_path)
    mix = json.loads((root / "bench/workloads/tiny-ssm.wake_zipf.json")
                     .read_text())
    mix.update(traffic="bursty_new", arrivals={"kind": "open_poisson",
                                               "rate_per_s": 2.0})
    (root / "bench/workloads/tiny-ssm.bursty_new.json").write_text(
        json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny-ssm.bursty_new",
                               "config": "tiny-ssm", "traffic": "bursty_new",
                               "chips": 1, "why": "a cell added as data"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = harness.load_spec("tiny-ssm.bursty_new", root)
    assert spec.conf["name"] == "tiny-ssm"
    assert [m["name"] for m in spec.end_to_end] == \
        [m["name"] for m in bench["end_to_end"]]
    # metrics listed for other cells only are left out
    assert "wake_ms.p50" not in [m["name"] for m in spec.per_layer]
    plans = traffic.Traffic(spec.mix, spec.conf["vocab_size"], 3
                            ).open_loop(5.0)
    assert len(plans) == 10
