"""A configuration joins the benchmark by its own files, and a ``waves``
cell may probe the device path before its first full-size session. On the
CPU at a tiny scale, in a root the test writes (conftest.tiny_root)."""

import json
import time

import pytest

from conftest import BENCH, add_config, add_traffic, shrink

# the traffic a cfg7-sized backlog runs: cfg5.backlog's waves, and a probe
# of 320 gangs of 8 (2,560 tasks, over the device path's 2,048-task gate)
PROBING = {"mode": "waves", "trace_sessions": 2, "path_probe_gangs": 320}
TPUSCORE_TIER = "- plugins:\n  - name: tpuscore\n"


def paper_2x() -> dict:
    """clusters.py's cfg7 (_paper_2x) at full size: cfg5's shapes and
    policy, 12,500 gangs of 8 on 50,000 nodes, drawn from Random(7); with
    its own tiny copy."""
    with open(f"{BENCH}/configs/cfg5-full-default.json") as f:
        cfg = json.load(f)
    cfg.update(name="cfg7-paper-2x", draw_seed=7,
               tiny={"nodes": 40, "counts": [40]})
    cfg["nodes"]["count"] = 50000
    cfg["groups"][0]["count"] = 12500
    return cfg


def join(root, cfg: dict, traffic: dict, name: str = "cfg7.backlog") -> str:
    """A configuration file, a traffic file and a workloads entry: all a
    new cell of a new configuration brings."""
    add_config(root, cfg)
    add_traffic(root, name.replace(".", "_"), traffic)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": name, "config": cfg["name"],
        "traffic": name.replace(".", "_"), "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return name


def main(root, cell: str, capsys, seed: int = 2 ** 31 + 11):
    """run.main on one cell: exit code, stdout, and the run's record (the
    first JSON line of stderr), or stderr where there is none."""
    import run

    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   "2", "--trace", "0"], root=str(root))
    cap = capsys.readouterr()
    lines = [ln for ln in cap.err.splitlines() if ln.startswith("{")]
    return rc, cap.out, json.loads(lines[0]) if lines else cap.err


def test_configuration_added_by_files_alone(tiny_root, capsys):
    cell = join(tiny_root, paper_2x(), PROBING)
    written = json.loads(
        (tiny_root / "benchmark/configs/cfg7-paper-2x.json").read_text())
    assert written["nodes"]["count"] == 40
    assert written["groups"][0]["count"] == 40
    rc, out, rec = main(tiny_root, cell, capsys)
    assert rc == 0
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["metrics"]["session_ms"]["value"] > 0
    assert rec["device_sessions"] == rec["sessions"]


@pytest.mark.parametrize("tiny,match", [
    (None, 'no "tiny" entry'),
    ({"nodes": 40, "counts": [40, 8]}, "2 group counts for 1 group class"),
])
def test_configuration_without_its_tiny_copy_is_named(tiny, match):
    cfg = paper_2x()
    if tiny is None:
        del cfg["tiny"]
    else:
        cfg["tiny"] = tiny
    with pytest.raises(ValueError, match=match):
        shrink(cfg)


@pytest.mark.parametrize("why,change,text", [
    # no tpuscore tier: the program stays serial
    ("serial policy", lambda c: c.update(
        policy=c["policy"].replace(TPUSCORE_TIER, "")), '"mode": null'),
    # 40 nodes of 64Ti reach 2^31 MiB in all: the encoder's int32 guard
    ("memory total", lambda c: c["nodes"].update(memory="64Ti"),
     "cluster capacity exceeds int32 quantized-bound range"),
])
def test_probe_off_the_device_path_ends_the_run(tiny_root, capsys, why,
                                                 change, text):
    cfg = paper_2x()
    change(cfg)
    cell = join(tiny_root, cfg, PROBING)
    t0 = time.perf_counter()
    rc, out, err = main(tiny_root, cell, capsys)
    assert time.perf_counter() - t0 < 5
    assert rc == 2
    assert out == ""
    assert "the probe session left the device path" in err
    assert text in err
    assert "nothing measured" in err


def test_probe_passes_and_stays_out_of_the_window(tiny_root, capsys,
                                                  monkeypatch):
    import traffic

    seen = []
    orig = traffic.Driver._session

    def _session(self, cl, sess, phase):
        rec = orig(self, cl, sess, phase)
        seen.append(rec)
        return rec

    monkeypatch.setattr(traffic.Driver, "_session", _session)
    cell = join(tiny_root, paper_2x(), PROBING)
    rc, out, rec = main(tiny_root, cell, capsys)
    assert rc == 0
    assert json.loads(out.strip().splitlines()[-1])["correct"]
    probe, rest = seen[0], seen[1:]
    assert probe["phase"] == "probe"
    assert probe["profile"]["mode"] == "rounds"
    # 16 tiny probe gangs of 8 on the empty cluster, every one bound
    assert probe["pending"] == len(probe["binds"]) == 128
    assert all("-probe-" in k for k, _ in probe["binds"])
    assert not any(sum(r["check"].values()) for r in seen)
    assert [r["phase"] for r in rest[:2]] == ["warm", "warm"]
    window = [r for r in rest if r["phase"] == "window"]
    assert window
    assert not any("probe" in k for r in window for k, _ in r["binds"])
    assert all(r["pending"] == 320 for r in rest)


def test_waves_without_the_key_run_as_before(tiny_root, capsys):
    """cfg5.backlog's traffic has no probe: two warm sessions, then the
    window, each on a whole wave of 40 gangs of 8."""
    rc, _, rec = main(tiny_root, "cfg5.backlog", capsys)
    assert rc == 0
    phases = [s[0] for s in rec["per_session"]]
    assert phases[:2] == ["warm", "warm"]
    assert set(phases[2:]) == {"window"}
    assert [s[2] for s in rec["per_session"]] == [320] * len(phases)


def test_unchanged_state_ends_a_probing_run(tiny_root, capsys, monkeypatch):
    """Actions that do nothing leave the device path in the probe: the run
    ends there, with nothing measured."""
    from volcano_tpu.scheduler import framework

    monkeypatch.setattr(framework, "run_actions", lambda ssn, actions: {})
    cell = join(tiny_root, paper_2x(), PROBING)
    rc, out, err = main(tiny_root, cell, capsys)
    assert rc == 2 and out == ""
    assert '{"mode": null}' in err


@pytest.mark.parametrize("fault", ["_half", "_altered"])
def test_broken_binds_in_a_probing_run_are_not_correct(tiny_root, capsys,
                                                       monkeypatch, fault):
    import test_faults

    test_faults._break_binder(monkeypatch, getattr(test_faults, fault))
    cell = join(tiny_root, paper_2x(), PROBING)
    rc, out, _ = main(tiny_root, cell, capsys)
    assert rc == 0
    assert not json.loads(out.strip().splitlines()[-1])["correct"]


def test_control_passes_the_probe_and_is_not_correct(tiny_root, capsys,
                                                     monkeypatch):
    """The reference in the program's place has no device path to leave;
    it reaches the window and breaks the capacity guarantee there."""
    import control
    import traffic

    monkeypatch.setattr(traffic, "Session", traffic.Session)
    monkeypatch.setattr(traffic, "Fallbacks", traffic.Fallbacks)
    monkeypatch.setattr(traffic.Driver, "_session", traffic.Driver._session)
    control.install()
    cell = join(tiny_root, paper_2x(), PROBING)
    rc, out, _ = main(tiny_root, cell, capsys)
    assert rc == 0
    res = json.loads(out.strip().splitlines()[-1])
    assert not res["correct"]
    assert res["checks"]["violations"]["value"] > 0
