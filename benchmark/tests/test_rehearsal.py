"""Each cell's run, end to end, at a tiny scale on the CPU: the harness finds
the cell, its configuration, traffic and metric readers by name in a root
the test wrote, runs warm-up, window and checks, and prints the contract's
last line."""

import json
import os

import pytest

from conftest import ROOT, run_cell

CELLS = ["cfg5.backlog", "cfg4.preempt", "cfg5.steady"]
# every cell BENCHMARK.json lists, those that later entries add with it
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    ALL_CELLS = [w["name"] for w in json.load(_f)["workloads"]]


def _bench(root):
    return json.loads((root / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_window_is_correct_and_reports_its_metrics(tiny_root, capsys, cell):
    res = run_cell(tiny_root, cell, capsys=capsys)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {m["name"] for m in _bench(tiny_root)["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_host_spans(tiny_root, capsys, cell):
    """On the CPU the trace has no device plane: the device metrics are
    left out, never reported as 0; the host spans are there."""
    res = run_cell(tiny_root, cell, trace=1, capsys=capsys)
    assert res["correct"], res["checks"]
    assert {"open_ms", "actions_ms", "close_ms"} <= set(res["metrics"])
    assert "device_busy_ms" not in res["metrics"]
    assert "busy_s" not in res["device"]


def test_cell_added_by_files_alone(tiny_root, capsys):
    """A new cell is a new traffic file and a new entry: no code changes."""
    bench = _bench(tiny_root)
    traffic = json.loads(
        (tiny_root / "benchmark/traffic/backlog.json").read_text())
    (tiny_root / "benchmark/traffic/backlog_again.json").write_text(
        json.dumps(traffic))
    bench["workloads"].append({
        "name": "cfg5.again", "config": "cfg5-full-default",
        "traffic": "backlog_again", "chips": 1, "why": "test"})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    res = run_cell(tiny_root, "cfg5.again", capsys=capsys)
    assert res["correct"], res["checks"]
    assert "session_ms" in res["metrics"]


def test_traffic_mode_added_by_file_alone(tiny_root, capsys):
    """A new way of offering work is a new file under modes/, found by the
    name a traffic file gives."""
    (tiny_root / "benchmark/modes/once.py").write_text(
        "import traffic\n"
        "from cluster import Cluster\n"
        "from harness import Recorder\n\n\n"
        "class Driver(traffic.Driver):\n"
        "    def __init__(self, *a):\n"
        "        super().__init__(*a)\n"
        "        self.rec = Recorder()\n"
        "        self.cl = Cluster(self.cfg, self.seed,\n"
        "                          self.new_cache(self.rec))\n"
        "        self.cl.populate(self.cl.add_nodes())\n"
        "        self.sess = self.new_session(self.cl.cache, self.rec,\n"
        "                                     self.cfg['policy'])\n\n"
        "    def warm(self):\n"
        "        pass\n\n"
        "    def step(self, deadline):\n"
        "        if self.records:\n"
        "            return None\n"
        "        return self._session(self.cl, self.sess, 'window')\n")
    (tiny_root / "benchmark/traffic/once.json").write_text(
        json.dumps({"mode": "once", "trace_sessions": 1}))
    bench = _bench(tiny_root)
    bench["workloads"].append({
        "name": "cfg5.once", "config": "cfg5-full-default",
        "traffic": "once", "chips": 1, "why": "test"})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    res = run_cell(tiny_root, "cfg5.once", capsys=capsys)
    assert res["attempted"] > 0
    assert res["checks"]["unbound"]["value"] == 0
    assert "session_ms" in res["metrics"]


def test_metric_added_by_file_alone(tiny_root, capsys):
    bench = _bench(tiny_root)
    bench["end_to_end"].append({
        "name": "sessions_in_window", "unit": "1", "better": "higher",
        "bound": 0.25, "source": "host_clock"})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    (tiny_root / "benchmark/metrics/sessions_in_window.py").write_text(
        "def read(run):\n    return len(run.sessions)\n")
    res = run_cell(tiny_root, "cfg5.backlog", capsys=capsys)
    assert res["metrics"]["sessions_in_window"]["value"] >= 1


def test_refuses_without_a_tpu(tiny_root, capsys, monkeypatch):
    import run

    monkeypatch.undo()  # the real device check: this process has no TPU
    rc = run.main(["--workload", "cfg5.backlog", "--seed", "1", "--seconds",
                   "1", "--trace", "0"], root=str(tiny_root))
    assert rc == 2
    assert capsys.readouterr().out == ""


def test_refuses_a_directory_without_the_program(tmp_path):
    """A checkout that holds only BENCHMARK.json and benchmark/ has no
    program to run: non-zero exit, no result."""
    import shutil
    import subprocess
    import sys

    from conftest import BENCH, ROOT

    shutil.copy(f"{ROOT}/BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".jax_cache", "__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "cfg5.backlog",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout == ""
