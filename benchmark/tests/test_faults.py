"""A run with the timed path broken underneath reads ``correct: false``,
for each fault a cell can have, and so does the control (the reference in
the program's place, one guarantee broken). The preempt cell can also
evict more than its preemptors need. The device check is steered
as in test_rehearsal; everything else is a whole run. One chip per cell:
no exchange between chips to leave out."""

import pytest

from conftest import run_cell

CELLS = ["cfg5.backlog", "cfg4.preempt", "cfg5.steady"]


class _Proxy:
    """The binder as the program calls it, with a fault on the way."""

    KEYED_NEEDS_PODS = False

    def __init__(self, inner, fault):
        self.inner, self.fault = inner, fault

    def bind(self, pod, hostname):
        self.bind_many([(pod, hostname)])

    def bind_many(self, pairs):
        pairs = list(pairs)
        keys = [f"{p.metadata.namespace}/{p.metadata.name}" for p, _ in pairs]
        self.bind_many_keyed(keys, None, [h for _, h in pairs])

    def bind_many_keyed(self, keys, pods, hosts):
        keys, hosts = self.fault(list(keys), list(hosts))
        self.inner.bind_many_keyed(keys, None, hosts)

    def __getattr__(self, name):
        return getattr(self.inner, name)


def _half(keys, hosts):
    """Half of the batch left out."""
    n = len(keys) // 2
    return keys[:n], hosts[:n]


def _altered(keys, hosts):
    """One answer altered where it is produced: a bind to no node."""
    if hosts:
        hosts[0] = "node-that-does-not-exist"
    return keys, hosts


def _break_binder(monkeypatch, fault):
    import traffic

    orig = traffic.new_cache

    def new_cache(recorder):
        cache = orig(recorder)
        cache.binder = _Proxy(recorder, fault)
        return cache

    monkeypatch.setattr(traffic, "new_cache", new_cache)


@pytest.mark.parametrize("cell", CELLS)
def test_unchanged_state_is_not_correct(tiny_root, capsys, monkeypatch, cell):
    """A session that returns its state unchanged: actions do nothing."""
    from volcano_tpu.scheduler import framework

    monkeypatch.setattr(framework, "run_actions", lambda ssn, actions: {})
    res = run_cell(tiny_root, cell, capsys=capsys)
    assert not res["correct"]
    assert res["checks"]["unbound"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_half_the_batch_is_not_correct(tiny_root, capsys, monkeypatch, cell):
    _break_binder(monkeypatch, _half)
    res = run_cell(tiny_root, cell, capsys=capsys)
    assert not res["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_is_not_correct(tiny_root, capsys, monkeypatch, cell):
    _break_binder(monkeypatch, _altered)
    res = run_cell(tiny_root, cell, capsys=capsys)
    assert not res["correct"]
    assert res["checks"]["violations"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tiny_root, capsys, monkeypatch, cell):
    """The reference's placement without its cpu check breaks the capacity
    guarantee on every cell's cluster."""
    import control
    import traffic

    monkeypatch.setattr(traffic, "Session", traffic.Session)
    monkeypatch.setattr(traffic, "Fallbacks", traffic.Fallbacks)
    monkeypatch.setattr(traffic.Driver, "_session", traffic.Driver._session)
    control.install()
    res = run_cell(tiny_root, cell, capsys=capsys)
    assert not res["correct"]
    assert res["checks"]["violations"]["value"] > 0


def test_evicting_every_permitted_victim_is_not_correct(tiny_root, capsys,
                                                       monkeypatch):
    """The evictor receives every permitted victim (each running gang down
    to its minMember), not only those the preemptors need."""
    import faults
    import harness
    import traffic

    orig_run, orig_session = harness.Session.run, traffic.Driver._session

    def _session(self, cl, sess, phase):
        sess.world = cl.world
        return orig_session(self, cl, sess, phase)

    def run(self, span="bench.session"):
        rec = orig_run(self, span)
        rec["evicts"] = faults.evict_all(self.world, rec["evicts"])
        return rec

    monkeypatch.setattr(traffic.Driver, "_session", _session)
    monkeypatch.setattr(harness.Session, "run", run)
    res = run_cell(tiny_root, "cfg4.preempt", capsys=capsys)
    assert not res["correct"]
    assert res["checks"]["over_evicted"]["value"] > 0
    assert res["checks"]["violations"]["value"] == 0
