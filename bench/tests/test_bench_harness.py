"""The harness: refusal without a known chip, metric selection, and
discovery of configurations, traffic and metrics added as files."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import generators, harness

ROOT = Path(__file__).resolve().parents[2]


def _fake_devices(monkeypatch, platform, kind, count=1):
    import jax

    dev = SimpleNamespace(platform=platform, device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev] * count)


def test_refuses_a_cpu(monkeypatch):
    _fake_devices(monkeypatch, "cpu", "cpu")
    with pytest.raises(SystemExit, match="no accelerator"):
        harness.require_device(1)


def test_refuses_an_unknown_device_kind(monkeypatch):
    _fake_devices(monkeypatch, "tpu", "TPU v9 imaginary")
    with pytest.raises(SystemExit, match="not in bench/peaks.py"):
        harness.require_device(1)


def test_refuses_too_few_chips(monkeypatch):
    _fake_devices(monkeypatch, "tpu", "TPU v5 lite", count=1)
    with pytest.raises(SystemExit, match="needs 4 chips"):
        harness.require_device(4)
    _fake_devices(monkeypatch, "tpu", "TPU v5 lite", count=4)
    assert harness.require_device(4).device_kind == "TPU v5 lite"


def _run_py(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "minicpm-2b.decode-batch", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_with_no_result_on_a_cpu():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout and "no accelerator" in p.stderr


def test_run_exits_nonzero_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0 and "{" not in p.stdout


def test_metrics_for_a_cell():
    spec = harness.load_spec()
    e2e = {m["name"] for m in harness.metrics_for(
        spec, "minicpm-2b.decode-batch", False)}
    assert e2e == {"output_tok_s", "setup_s"}
    layer = harness.metrics_for(spec, "minicpm-2b.decode-batch", True)
    assert {m["moves"] for m in layer} == {"output_tok_s"}
    for m in spec["per_layer"] + spec["end_to_end"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()


def test_every_cell_reports_what_its_metrics_move():
    spec = harness.load_spec()
    for cell in spec["workloads"]:
        e2e = {m["name"] for m in harness.metrics_for(spec, cell["name"],
                                                      False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = harness.metrics_for(spec, cell["name"], True)
        assert layer and all(m["moves"] in e2e for m in layer)


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    """A later cell adds files and edits none: a configuration, a mix of
    an existing generator kind, a metric reader, and BENCHMARK.json
    entries naming them."""
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    conf = json.loads((bench / "configs" / "minicpm-2b.json").read_text())
    conf["name"] = "minicpm-2b-long"
    (bench / "configs" / "minicpm-2b-long.json").write_text(json.dumps(conf))
    (bench / "traffic" / "long-backlog.json").write_text(json.dumps({
        "generator": "backlog", "requests": 16,
        "prompt_len": {"dist": "uniform", "min": 1024, "max": 2048},
        "output_len": {"dist": "uniform", "min": 512, "max": 1024}}))
    (bench / "metrics" / "queue_wait_p90_ms.chat.py").write_text(
        "def read(run):\n    return 42.0 if run.record else None\n")
    spec = harness.load_spec()
    spec["configs"].append({"name": "minicpm-2b-long"})
    spec["workloads"].append({"name": "minicpm-2b-long.long-backlog",
                              "config": "minicpm-2b-long",
                              "traffic": "long-backlog", "chips": 1})
    spec["per_layer"].append({"name": "queue_wait_p90_ms.chat", "unit": "ms",
                              "moves": "output_tok_s",
                              "workloads": ["minicpm-2b-long.long-backlog"]})
    for p, data in before.items():
        assert p.read_bytes() == data        # nothing there was edited
    cell, c, mix = harness.cell_parts(spec, "minicpm-2b-long.long-backlog",
                                      bench=bench)
    assert c["name"] == "minicpm-2b-long" and mix["requests"] == 16
    work = generators.load(mix["generator"]).generate(
        mix, seed=2**33, seconds=10, vocab=100)
    assert len(work.requests) == 16
    names = [m["name"] for m in harness.metrics_for(
        spec, "minicpm-2b-long.long-backlog", True)]
    assert names == ["queue_wait_p90_ms.chat"]
    run = harness.Run(cell=cell, conf=c, record={"x": 1}, setup_s=1.0,
                      trace=None, peaks=None)
    assert harness.read_metric(names[0], run, bench=bench) == 42.0


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit, match="unknown workload"):
        harness.cell_parts(harness.load_spec(), "nope.chat")
