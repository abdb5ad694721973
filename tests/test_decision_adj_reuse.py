"""Decision decodes an ``adj:`` value against the value it last decoded
for that ``(area, key)``: what ``process_publication`` keeps, when it
drops it, that a bad value neither raises nor poisons it, that areas do
not share it -- and that none of this moved the arming of the debounce
timer (``process_publication`` first, then ``_rebuild_debounced``, the
policy asked for its minimum). Counts, identities and order; no time
but the policy's own 10.0."""

from __future__ import annotations

from dataclasses import replace

import pytest

from openr_tpu.decision.decision import Decision
from openr_tpu.messaging.queue import ReplicateQueue
from openr_tpu.models import topologies
from openr_tpu.types import AdjacencyDatabase, Publication, Value
from openr_tpu.utils import keys as keyutil
from openr_tpu.utils import wire

from test_decision_window_account import MIN_MS, Harness, _span

REUSED = "decision.adj_elements_reused"
DECODED = "decision.adj_elements_decoded"


@pytest.fixture
def decision():
    """A Decision fed by hand, as the load harness's oracle is: nothing
    started, ``process_publication`` called on this thread."""
    kv_q = ReplicateQueue(name="test:kvstore")
    d = Decision(
        "a",
        kvstore_updates_queue=kv_q,
        route_updates_queue=ReplicateQueue(name="test:routes"),
        solver_backend="host",
    )
    yield d
    kv_q.close()


def _star(n=6):
    """``hub`` with ``n`` adjacencies, each spoke with one."""
    return topologies.build_topology(
        "star", [("hub", f"s{i}", 10 + i) for i in range(n)])


def _publish(decision, db, area="0", version=1, **pub):
    key = keyutil.adj_key(db.this_node_name)
    value = Value(version=version, originator_id=db.this_node_name,
                  value=db if isinstance(db, bytes) else wire.dumps(db))
    decision.process_publication(
        Publication(key_vals={key: value}, area=area, **pub))
    return value


def _recost(db, k, metric):
    adjs = list(db.adjacencies)
    adjs[k] = replace(adjs[k], metric=metric)
    return replace(db, adjacencies=tuple(adjs))


def _counts(decision):
    return (decision.counters[REUSED], decision.counters[DECODED])


def _held(decision, node, area="0"):
    return decision.area_link_states[area].get_adjacency_databases()[node]


def test_a_republished_database_keeps_what_stands(decision):
    hub = _star().adj_dbs["hub"]
    first = _publish(decision, hub)
    assert _counts(decision) == (0, 6)
    memo = decision._adj_decoded[("0", "adj:hub")]
    assert memo.data is first.value  # KvStore's bytes, not a copy
    was = _held(decision, "hub").adjacencies

    second = _publish(decision, _recost(hub, 2, 99), version=2)
    assert _counts(decision) == (5, 7)
    now = _held(decision, "hub")
    assert now == wire.loads(second.value, AdjacencyDatabase)
    assert [a is b for a, b in zip(now.adjacencies, was)] \
        == [True, True, False, True, True, True]
    assert decision._adj_decoded[("0", "adj:hub")].data is second.value
    assert decision.counters["decision.adj_db_update"] == 2
    assert decision._collect_counters()[REUSED] == 5


@pytest.mark.parametrize("bad", [
    b"",
    b"\x00garbage",
    wire.dumps(_star().adj_dbs["hub"])[:97],
    wire.dumps(_star().adj_dbs["hub"]) + b"N",
    wire.dumps(_star().adj_dbs["s1"]),  # another node's, under this key
], ids=["empty", "garbage", "truncated", "trailing", "misnamed"])
def test_a_bad_value_is_skipped_and_poisons_nothing(decision, bad):
    hub = _star().adj_dbs["hub"]
    _publish(decision, hub)
    good = decision._adj_decoded[("0", "adj:hub")]
    before = _held(decision, "hub")

    key = keyutil.adj_key("hub")
    decision.process_publication(Publication(key_vals={
        key: Value(version=2, originator_id="hub", value=bad),
        # the same publication's other keys are still taken
        keyutil.adj_key("s0"): Value(
            version=1, originator_id="s0",
            value=wire.dumps(_star().adj_dbs["s0"])),
    }, area="0"))
    assert decision._adj_decoded[("0", "adj:hub")] is good
    assert _held(decision, "hub") is before
    assert "s0" in decision.area_link_states["0"].get_adjacency_databases()
    assert decision.counters["decision.adj_db_update"] == 2

    # the next good value of the key decodes against the last good one
    reused, decoded = _counts(decision)
    nxt = _recost(hub, 0, 77)
    _publish(decision, nxt, version=3)
    assert _held(decision, "hub") == nxt
    assert _counts(decision) == (reused + 5, decoded + 1)


def test_an_expired_key_drops_what_was_kept(decision):
    hub = _star().adj_dbs["hub"]
    _publish(decision, hub)
    _publish(decision, _star().adj_dbs["s0"])
    decision.process_publication(
        Publication(expired_keys=["adj:hub"], area="0"))
    assert ("0", "adj:hub") not in decision._adj_decoded
    assert ("0", "adj:s0") in decision._adj_decoded
    assert "hub" not in decision.area_link_states["0"] \
        .get_adjacency_databases()
    # it comes back as a key seen for the first time
    reused, decoded = _counts(decision)
    _publish(decision, hub, version=2)
    assert _counts(decision) == (reused, decoded + 6)


def test_a_ttl_refresh_leaves_it_alone(decision):
    hub = _star().adj_dbs["hub"]
    _publish(decision, hub)
    kept = decision._adj_decoded[("0", "adj:hub")]
    decision.process_publication(Publication(key_vals={
        "adj:hub": Value(version=1, originator_id="hub", value=None,
                         ttl_version=4)}, area="0"))
    assert decision._adj_decoded[("0", "adj:hub")] is kept
    assert _counts(decision) == (0, 6)


def test_two_areas_with_the_same_node_name_share_nothing(decision):
    hub = _star().adj_dbs["hub"]
    other = replace(_recost(hub, 1, 500), area="1")
    _publish(decision, hub, area="0")
    _publish(decision, other, area="1")
    # the second area's first value of the key: nothing taken from the
    # first area's, though five of its six elements are byte-equal
    assert _counts(decision) == (0, 12)
    assert set(decision._adj_decoded) == {("0", "adj:hub"), ("1", "adj:hub")}
    assert _held(decision, "hub", "0") == hub
    assert _held(decision, "hub", "1") == other

    _publish(decision, _recost(other, 3, 9), area="1", version=2)
    assert _counts(decision) == (5, 13)
    assert _held(decision, "hub", "0") == hub
    decision.process_publication(
        Publication(expired_keys=["adj:hub"], area="0"))
    assert set(decision._adj_decoded) == {("1", "adj:hub")}


def test_a_database_that_names_another_area_is_rewrapped(decision):
    """The ``adj_db.area != area`` re-wrap stands, and shares the kept
    adjacencies."""
    hub = replace(_star().adj_dbs["hub"], area="elsewhere")
    _publish(decision, hub, area="7")
    held = _held(decision, "hub", "7")
    assert held.area == "7" and held == replace(hub, area="7")
    kept = decision._adj_decoded[("7", "adj:hub")]
    assert kept.obj.area == "elsewhere"
    assert held.adjacencies is kept.obj.adjacencies


def test_every_event_kind_lands_as_a_fresh_decode_would(decision):
    """A stream of the traffic's edits through ``process_publication``
    leaves LinkState holding what ``wire.loads`` gives for the last
    bytes of each key."""
    topo = _star(8)
    dbs = dict(topo.adj_dbs)
    last = {}
    for node, db in dbs.items():
        last[node] = _publish(decision, db).value
    withdrawn = None
    for step in range(40):
        hub = dbs["hub"]
        adjs = list(hub.adjacencies)
        if step % 5 == 4 and withdrawn is None:
            withdrawn = adjs.pop(step % len(adjs))  # a flap: one side
        elif step % 5 == 2 and withdrawn is not None:
            adjs.append(withdrawn)  # ... and back, at the end
            withdrawn = None
        else:
            k = step % len(adjs)
            adjs[k] = replace(adjs[k], metric=(63, 64, 8191, 8192)[step % 4])
        dbs["hub"] = replace(hub, adjacencies=tuple(adjs))
        last["hub"] = _publish(decision, dbs["hub"], version=step + 2).value
    for node, data in last.items():
        assert _held(decision, node) == wire.loads(data, AdjacencyDatabase)
    reused, decoded = _counts(decision)
    # 8 + 8 bulk decodes, then one element a step at most
    assert decoded <= 16 + 40 and reused >= 40 * 6


# -- the arming order -----------------------------------------------------


@pytest.fixture
def harness():
    h = Harness()
    yield h
    h.stop()


def test_the_timer_is_armed_after_the_publication_is_processed(harness):
    """Upstream's order (processPublication -> rebuildRoutesDebounced_):
    the decode runs before the window opens, and the window still asks
    for the policy's minimum."""
    decision = harness.decision
    order = []
    process, arm = decision.process_publication, decision._rebuild_debounced
    prewarm = decision.spf_solver.prewarm

    def processed(pub):
        order.append("process>")
        process(pub)
        order.append("<process")

    class Armed(type(arm)):
        def __call__(self):
            order.append("arm")
            super().__call__()

    def prewarmed(*args, **kwargs):
        order.append("prewarm")
        return prewarm(*args, **kwargs)

    def swap():
        decision.process_publication = processed
        arm.__class__ = Armed
        decision.spf_solver.prewarm = prewarmed

    decision.evb.call_and_wait(swap)
    reused = decision.get_counters()[REUSED]
    harness.recost("b", 5)
    debounce = _span(harness.window(), "decision.debounce")
    assert order == ["process>", "<process", "arm", "prewarm"]
    assert debounce.attrs["policy_ms"] == pytest.approx(MIN_MS, abs=1e-6)
    # "b" re-cost both its adjacencies: the mechanism ran, and took none
    counters = decision.get_counters()
    assert counters[REUSED] == reused
    assert counters[DECODED] >= 2
