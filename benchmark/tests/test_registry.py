"""The harness finds a configuration, a traffic mix, a document shape, a
load and a metric by name, each from a file of its own: a new one is a
new file (and a new entry), with no edit to any file that is there."""
import json
import os

import pytest

from benchmark import harness, roofline
from benchmark.traffic import generator

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_every_entry_resolves_to_its_files():
    spec = harness.load_spec()
    for c in spec["configs"]:
        cfg = harness.load_config(spec, c["name"])
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    for w in spec["workloads"]:
        assert generator.load_mix(w["traffic"])
        assert w["config"] in {c["name"] for c in spec["configs"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.load_reader(m["name"]).read)


def test_each_cell_reports_setup_another_e2e_and_a_per_layer_metric():
    spec = harness.load_spec()
    for w in spec["workloads"]:
        e2e = {m["name"] for m in harness.metrics_for(spec, w["name"], False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = harness.metrics_for(spec, w["name"], True)
        assert layer and all(m["moves"] in e2e for m in layer)


def test_a_new_metric_file_is_found_without_an_edit(tmp_path):
    path = os.path.join(HERE, "metrics", "zz_dummy_probe.py")
    assert not os.path.exists(path)
    try:
        with open(path, "w") as f:
            f.write("def read(ctx):\n    return 42.0 + len(ctx.writes)\n")
        spec = harness.load_spec()
        spec["per_layer"].append({
            "name": "zz_dummy_probe.push", "unit": "ms", "better": "lower",
            "source": "program_span", "layer": "device",
            "moves": "acked_ops_per_s.push",
            "workloads": ["nested8_100k.push"]})
        names = [m["name"] for m in
                 harness.metrics_for(spec, "nested8_100k.push", True)]
        assert "zz_dummy_probe.push" in names
        reader = harness.load_reader("zz_dummy_probe.push")
        ctx = harness.Ctx(cell={}, config={}, traffic="push", window_s=1.0,
                          setup_s=1.0, writes=[1, 2], reads=[], spans={},
                          counters={}, commits=[], compiles=0, trace=None,
                          device={})
        assert reader.read(ctx) == 44.0
    finally:
        os.remove(path)


DUMMY_SHAPE = """
from benchmark.traffic import wire, workloads


def make_log(config, rng):
    ops = [("add", (0,), workloads.OFFSET * 7 + i + 1, i)
           for i in range(config["ops"])]
    return workloads.DocLog(body=wire.dumps_ops(ops), n_ops=len(ops),
                            ops=ops)
"""

DUMMY_LOAD = """
class Load:
    def __init__(self, traffic, params):
        self.traffic = traffic
        self.logs_needed = params["logs"]
        self.seen = []

    def prepare(self, server, mark):
        self.seen.append("prepare")

    def warm(self, server, mark):
        self.seen.append("warm")

    def threads(self, server, t0, stop):
        return []
"""


def test_a_new_shape_and_load_file_are_found_without_an_edit():
    from benchmark.traffic import generator
    paths = [os.path.join(HERE, "traffic", "shapes", "zz_dummy_shape.py"),
             os.path.join(HERE, "traffic", "loads", "zz_dummy_load.py")]
    assert not any(os.path.exists(p) for p in paths)
    try:
        for path, text in zip(paths, (DUMMY_SHAPE, DUMMY_LOAD)):
            with open(path, "w") as f:
                f.write(text)
        mix = {"about": "a dummy", "zz_dummy_load": {"logs": 3}}
        t = generator.Traffic(mix, {"shape": "zz_dummy_shape", "ops": 5}, 9)
        assert [lg.n_ops for lg in t.logs] == [5, 5, 5]
        t.setup(None, lambda name: None)
        assert t.loads[0].seen == ["prepare", "warm"]
    finally:
        for path in paths:
            if os.path.exists(path):
                os.remove(path)


def test_bytes_and_peaks():
    assert roofline.merge_bytes(1, 1) == 69 + 16
    assert roofline.merge_bytes(10**6, 8) == 10**6 * (69 + 128)
    assert roofline.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peak("TPU v9 imaginary")


def test_benchmark_json_keeps_to_the_contract_shape():
    spec = harness.load_spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "benchmark/run.py"]
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    layers = {m["layer"] for m in spec["per_layer"]}
    assert len(layers) <= 8
    for m in spec["per_layer"]:
        for w in m.get("workloads", []):
            assert w in {c["name"] for c in spec["workloads"]}
    assert len(json.dumps(spec)) < 64 * 1024


def test_no_tpu_means_no_result(capsys):
    from benchmark import run
    rc = run.main(["--workload", "nested8_100k.push", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""
