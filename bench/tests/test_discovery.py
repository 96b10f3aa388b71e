"""A configuration, a traffic mix, a runner and a per-layer metric added as
files, with entries in a benchmark file, are found by name: no harness code
changes."""
import json
import textwrap

import jax

from bench import harness

RUNNER = '''
def run(ctx):
    ctx.setup_done()
    with ctx.window():
        pass
    ctx.read_memory()
    return {"metrics": {"dummy_ms": ctx.config["answer"]
                        + ctx.traffic["offset"]},
            "attempted": 7, "failed": 0,
            "numbers": {"dummy_gap": 0.5}, "records": {"steps": 7}}
'''
METRIC = '''
def read(records):
    return records["steps"] * 2 + records["compiles_in_window"]
'''


def make_bench(tmp_path):
    for d in ("configs", "traffic", "runners", "layer_metrics"):
        (tmp_path / d).mkdir()
    (tmp_path / "configs" / "dummy-model.json").write_text(json.dumps(
        {"answer": 40.0, "matmul_precision": "highest"}))
    (tmp_path / "traffic" / "dummy.mix.json").write_text(json.dumps(
        {"runner": "dummy", "offset": 2.0, "limits": {"dummy_gap": 1.0}}))
    (tmp_path / "runners" / "dummy.py").write_text(textwrap.dedent(RUNNER))
    (tmp_path / "layer_metrics" / "dummy_share.x.py").write_text(
        textwrap.dedent(METRIC))
    spec = {
        "workloads": [{"name": "dummy-model.mix", "config": "dummy-model",
                       "traffic": "dummy.mix", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "dummy_ms", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock",
             "workloads": ["dummy-model.mix"]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "dummy_share.x", "unit": "%", "better": "higher",
             "source": "host_clock", "layer": "test", "moves": "dummy_ms"},
            {"name": "elsewhere", "unit": "%", "better": "higher",
             "source": "host_clock", "layer": "test", "moves": "other_ms"}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(tmp_path)


def test_files_alone_make_a_cell(tmp_path):
    d = make_bench(tmp_path)
    c = harness.resolve("dummy-model.mix", d, d + "/BENCHMARK.json")
    assert [m["name"] for m in c["e2e"]] == ["dummy_ms", "setup_s"]
    # a metric without "workloads" goes to every cell reporting what it moves
    assert [m["name"] for m in c["layer"]] == ["dummy_share.x"]
    args = harness.parse(["--workload", "dummy-model.mix", "--seed",
                          str(2**33 + 5), "--seconds", "1"])
    result = harness.execute(c, args, 0.0, jax.devices()[:1],
                             {"flops_per_s": 1.0, "bytes_per_s": 1.0})
    assert result["correct"] is True
    assert result["attempted"] == 7
    assert result["metrics"]["dummy_ms"] == {"value": 42.0, "unit": "ms"}
    assert set(result["metrics"]) == {"dummy_ms", "setup_s"}
    assert result["device"]["count"] == 1
    assert list(result)[-1] == "checks"
    assert result["checks"] == {"dummy_gap": {"value": 0.5, "limit": 1.0}}
    metric = harness.load_module("layer_metrics", "dummy_share.x", d)
    assert metric.read({"steps": 7, "compiles_in_window": 0}) == 14


def test_a_number_over_its_limit_is_not_correct(tmp_path):
    d = make_bench(tmp_path)
    traffic = tmp_path / "traffic" / "dummy.mix.json"
    t = json.loads(traffic.read_text())
    t["limits"]["dummy_gap"] = 0.25
    traffic.write_text(json.dumps(t))
    c = harness.resolve("dummy-model.mix", d, d + "/BENCHMARK.json")
    args = harness.parse(["--workload", "dummy-model.mix", "--seed", "1",
                          "--seconds", "1"])
    result = harness.execute(c, args, 0.0, jax.devices()[:1], {})
    assert result["correct"] is False
