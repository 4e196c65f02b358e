"""The harness finds a cell's parts by name, prints the contract's
result line, and refuses to run without a chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness

from . import tiny

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REQUIRED = {"correct", "attempted", "failed", "metrics", "device"}
EXTRA = {"compiles_in_window", "checks"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.write(str(tmp_path_factory.mktemp("bench-tree")))


def test_parts_added_as_new_files_are_found_by_name(tmp_path):
    """A configuration, a traffic mix and a per-layer metric that a later
    change adds as new files, next to an unchanged copy of the real
    tree, are found by their names alone."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}
    cfg = dict(tiny.CONFIGS["tiny-mnist"], name="new-net")
    (tmp_path / "bench/configs/new-net.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/new-mix.json").write_text(
        json.dumps(tiny.TRAFFIC["closed"]))
    (tmp_path / "bench/metrics/new_metric.closed.py").write_text(
        "def read(r):\n    return 42.0\n")
    spec["workloads"].append({"name": "new-cell", "config": "new-net",
                              "traffic": "new-mix", "chips": 1,
                              "why": "added"})
    spec["per_layer"].append({"name": "new_metric.closed", "unit": "%",
                              "better": "higher", "source": "host_clock",
                              "layer": "device",
                              "moves": "serve_images_per_s",
                              "workloads": ["new-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    changed = [p for p, b in before.items()
               if p.name != "BENCHMARK.json" and p.read_bytes() != b]
    assert changed == []
    ctx = harness.resolve(str(tmp_path), "new-cell")
    assert ctx.config["name"] == "new-net"
    assert ctx.traffic["in_flight"] == tiny.TRAFFIC["closed"]["in_flight"]
    names = [m["name"] for m in harness.metrics_of(str(tmp_path), "new-cell",
                                                   True)]
    assert names == ["new_metric.closed"]
    reader = harness.load_module(harness.part(
        str(tmp_path), "metrics", "new_metric.closed", ".py"), "m")
    assert reader.read(None) == 42.0
    with pytest.raises(FileNotFoundError):
        harness.part(str(tmp_path), "traffic", "no-such-mix")


def test_every_real_cell_resolves_and_names_its_metrics():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in spec["workloads"]:
        ctx = harness.resolve(ROOT, w["name"])
        assert os.path.isfile(os.path.join(
            ROOT, "bench", "drivers", ctx.traffic["kind"] + ".py"))
        assert os.path.isfile(os.path.join(ROOT, "bench", "limits",
                                           w["name"] + ".json"))
        e2e = {m["name"] for m in harness.metrics_of(ROOT, w["name"], False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = harness.metrics_of(ROOT, w["name"], True)
        assert layer
        for m in layer:
            harness.part(ROOT, "metrics", m["name"], ".py")
            assert m["moves"] in e2e


def test_result_line_has_the_contract_keys(root):
    out = tiny.run(root, "t-closed")
    assert REQUIRED <= set(out) and set(out) - REQUIRED == EXTRA
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"serve_images_per_s", "setup_s"}
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}
    assert out["correct"] is True
    assert out["compiles_in_window"] == 0
    json.dumps(out, allow_nan=False)


def test_traced_line_reports_per_layer_metrics(root, tmp_path):
    out = tiny.run(root, "t-train", trace=True, trace_dir=str(tmp_path))
    assert set(out) - REQUIRED == EXTRA | {"breakdown"}
    assert set(out["metrics"]) == {"setup_per_window"}
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert out["correct"] is True


def test_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "m3-serve-closed", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
