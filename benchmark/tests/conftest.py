"""CPU rehearsal of the benchmark: every cell of BENCHMARK.json at a tiny
scale, written by the test into a root of its own.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

The chip's checks are steered here, inside the tests: the device check
accepts the CPU, and the device path's task gate is lowered so that tiny
sessions still take it where the cell's full-size sessions do.
"""

import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# node count and per-class group counts of the tiny copies of the
# configurations that came before the "tiny" entry (cfg4 keeps its
# 8000:5000:1750:250:500 proportions); any other configuration states its
# own as "tiny": {"nodes": n, "counts": [groups per class]}
TINY = {"cfg5-full-default": (40, [40]),
        "cfg4-overcommit": (160, [100, 35, 5, 10])}
TINY_TRAFFIC = {"warm_s": 2.0, "drain_s": 5.0, "probe_gangs": 16,
                "path_probe_gangs": 16, "rate_gangs_per_s": 2.0}
# tasks: the tiny backlog, preempt and probe sessions take the device path,
# the open loop's other sessions (a few gangs each) stay serial, as at full
# size
GATE = 96


def shrink(cfg: dict) -> dict:
    """The configuration's tiny copy, from its ``tiny`` entry or, for the
    configurations that have none, its row of TINY."""
    cfg = json.loads(json.dumps(cfg))
    if "tiny" in cfg:
        nodes, counts = cfg["tiny"]["nodes"], cfg["tiny"]["counts"]
    elif cfg["name"] in TINY:
        nodes, counts = TINY[cfg["name"]]
    else:
        raise ValueError(
            f"configuration {cfg['name']!r} has no \"tiny\" entry: the CPU "
            "rehearsal needs \"tiny\": {\"nodes\": n, \"counts\": [groups "
            "per class]} in its file")
    if len(counts) != len(cfg["groups"]):
        raise ValueError(
            f"configuration {cfg['name']!r}: \"tiny\" gives {len(counts)} "
            f"group counts for {len(cfg['groups'])} group classes")
    cfg["nodes"]["count"] = nodes
    for cls, n in zip(cfg["groups"], counts):
        cls["count"] = n
    return cfg


def add_config(root, cfg: dict) -> None:
    """A configuration's tiny copy, into the root's configs."""
    (root / "benchmark" / "configs" / (cfg["name"] + ".json")).write_text(
        json.dumps(shrink(cfg)))


def add_traffic(root, name: str, tr: dict) -> None:
    """A traffic mix, its lengths, rates and counts cut to the tiny size,
    into the root's traffic."""
    tr = dict(tr)
    tr.update({k: v for k, v in TINY_TRAFFIC.items() if k in tr})
    (root / "benchmark" / "traffic" / (name + ".json")).write_text(
        json.dumps(tr))


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """A checkout-shaped directory holding BENCHMARK.json and tiny copies of
    its configurations and traffic; run.main runs its cells on the CPU."""
    import run
    from volcano_tpu.ops.solver import BatchAllocator

    root = tmp_path / "root"
    base = root / "benchmark"
    (base / "configs").mkdir(parents=True)
    (base / "traffic").mkdir()
    for sub in ("metrics", "modes"):
        shutil.copytree(os.path.join(BENCH, sub), base / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for name in os.listdir(os.path.join(BENCH, "configs")):
        with open(os.path.join(BENCH, "configs", name)) as f:
            add_config(root, json.load(f))
    for name in os.listdir(os.path.join(BENCH, "traffic")):
        with open(os.path.join(BENCH, "traffic", name)) as f:
            add_traffic(root, name[:-len(".json")], json.load(f))
    monkeypatch.setattr(run, "check_device", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "device_peaks", lambda kind: {})
    monkeypatch.setattr(run, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(BatchAllocator, "AUTO_ROUNDS_THRESHOLD", GATE)
    return root


def run_cell(root, cell: str, seed: int = 2 ** 31 + 7, seconds: float = 2.0,
             trace: int = 0, capsys=None) -> dict:
    """run.main on one cell; the result's last stdout line, parsed."""
    import run

    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], root=str(root))
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])
