"""``DecisionRouteDb.calculate_update`` against the plain diff it replaced.

The reference below is the two loops per table the method used to be:
every pair goes to ``__eq__``. The method now settles by object identity
what it can and compares the rest, and must give the same
``DecisionRouteUpdate`` for every input: the same keys in the same
order, and the very entry objects of the new db.
"""

from __future__ import annotations

import copy

import pytest

from openr_tpu.decision.rib import (
    DecisionRouteDb,
    DecisionRouteUpdate,
    RibMplsEntry,
    RibUnicastEntry,
)
from openr_tpu.types import BinaryAddress, IpPrefix, NextHop, PrefixEntry


def reference_diff(old: DecisionRouteDb, new: DecisionRouteDb) -> DecisionRouteUpdate:
    delta = DecisionRouteUpdate()
    for prefix, entry in new.unicast_routes.items():
        was = old.unicast_routes.get(prefix)
        if was is None or was != entry:
            delta.unicast_routes_to_update[prefix] = entry
    for prefix in old.unicast_routes:
        if prefix not in new.unicast_routes:
            delta.unicast_routes_to_delete.append(prefix)
    for label, entry in new.mpls_routes.items():
        was = old.mpls_routes.get(label)
        if was is None or was != entry:
            delta.mpls_routes_to_update.append(entry)
    for label in old.mpls_routes:
        if label not in new.mpls_routes:
            delta.mpls_routes_to_delete.append(label)
    return delta


def nh(i: int, metric: int = 1) -> NextHop:
    return NextHop(
        address=BinaryAddress.from_str(f"fe80::{i + 1}"),
        metric=metric,
        area="0",
        neighbor_node_name=f"fsw-{i}",
    )


def prefix(i: int) -> IpPrefix:
    return IpPrefix.from_str(f"fc00:0:{i:x}::1/128")


def unicast(i: int, hops=(0, 1, 2), **over) -> RibUnicastEntry:
    p = prefix(i)
    return RibUnicastEntry(
        p, {nh(h) for h in hops}, PrefixEntry(prefix=p), over.pop("area", "0"),
        **over,
    )


def mpls(label: int, hops=(0, 1)) -> RibMplsEntry:
    return RibMplsEntry(label, {nh(h, metric=2) for h in hops})


def installed(n: int = 6) -> DecisionRouteDb:
    db = DecisionRouteDb()
    for i in range(n):
        db.add_unicast_route(unicast(i))
        db.add_mpls_route(mpls(100 + i))
    return db


def same_objects(old: DecisionRouteDb) -> DecisionRouteDb:
    """What a route build hands back when nothing was re-derived: new
    tables holding the installed objects."""
    return DecisionRouteDb(dict(old.unicast_routes), dict(old.mpls_routes))


# each case: installed db -> (new db, unicast prefixes expected in the
# delta, unicast deletes, mpls labels updated, mpls deletes, identical)
def case_same_objects(old):
    return same_objects(old), [], [], [], [], 12


def case_equal_but_distinct_objects(old):
    return copy.deepcopy(old), [], [], [], [], 0


def case_changed_next_hops(old):
    new = same_objects(old)
    new.add_unicast_route(unicast(2, hops=(0, 1)))
    new.add_mpls_route(mpls(104, hops=(0, 3)))
    return new, [prefix(2)], [], [104], [], 10


def case_best_area_only(old):
    new = same_objects(old)
    new.add_unicast_route(unicast(3, area="1"))
    return new, [], [], [], [], 11


def case_do_not_install_flipped(old):
    new = same_objects(old)
    new.add_unicast_route(unicast(1, do_not_install=True))
    return new, [prefix(1)], [], [], [], 11


def case_adds(old):
    new = same_objects(old)
    new.add_unicast_route(unicast(40))
    new.add_unicast_route(unicast(41))
    new.add_mpls_route(mpls(900))
    return new, [prefix(40), prefix(41)], [], [900], [], 12


def case_deletes(old):
    new = same_objects(old)
    # out of installed order, to show the delete list keeps that order
    for i in (4, 0):
        del new.unicast_routes[prefix(i)]
    for label in (105, 101, 103):
        del new.mpls_routes[label]
    return new, [], [prefix(0), prefix(4)], [], [101, 103, 105], 7


def case_adds_deletes_and_changes_together(old):
    new = same_objects(old)
    del new.unicast_routes[prefix(5)]
    new.add_unicast_route(unicast(1, hops=(2,)))
    new.add_unicast_route(unicast(77))
    del new.mpls_routes[100]
    new.add_mpls_route(mpls(555))
    return new, [prefix(1), prefix(77)], [prefix(5)], [555], [100], 9


def case_key_order_differs(old):
    # what the bulk-reuse build does: the re-derived prefixes are
    # popped and come back at the end of the table
    new = DecisionRouteDb(
        dict(reversed(list(old.unicast_routes.items()))),
        dict(reversed(list(old.mpls_routes.items()))),
    )
    new.add_unicast_route(new.unicast_routes.pop(prefix(4)))
    moved = unicast(2, hops=(1,))
    del new.unicast_routes[prefix(2)]
    new.add_unicast_route(moved)
    return new, [prefix(2)], [], [], [], 11


def case_entry_mutated_in_place(old):
    # rib_policy edits the entry it is handed: one object on both
    # sides, which equals itself whatever was done to it
    new = same_objects(old)
    new.unicast_routes[prefix(0)].nexthops.pop()
    new.mpls_routes[102].nexthops.add(nh(9))
    return new, [], [], [], [], 12


def case_both_empty(old):
    old.unicast_routes.clear()
    old.mpls_routes.clear()
    return DecisionRouteDb(), [], [], [], [], 0


def case_installed_empty(old):
    new = same_objects(old)
    labels = list(new.mpls_routes)
    prefixes = list(new.unicast_routes)
    old.unicast_routes.clear()
    old.mpls_routes.clear()
    return new, prefixes, [], labels, [], 0


def case_new_empty(old):
    return (DecisionRouteDb(), [], list(old.unicast_routes), [],
            list(old.mpls_routes), 0)


def case_mpls_label_replaced(old):
    new = same_objects(old)
    del new.mpls_routes[103]
    new.add_mpls_route(mpls(203))
    return new, [], [], [203], [103], 11


CASES = [v for k, v in sorted(globals().items()) if k.startswith("case_")]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__[5:])
def test_calculate_update_equals_the_plain_diff(case):
    old = installed()
    new, u_upd, u_del, m_upd, m_del, identical = case(old)
    before = copy.deepcopy(old)
    want = reference_diff(old, new)
    got = old.calculate_update(new)

    # the case is what its name says (the reference agrees with the
    # hand-written expectation), and the method agrees with both
    assert list(want.unicast_routes_to_update) == u_upd
    assert want.unicast_routes_to_delete == u_del
    assert [e.label for e in want.mpls_routes_to_update] == m_upd
    assert want.mpls_routes_to_delete == m_del
    assert got == want
    assert list(got.unicast_routes_to_update) == u_upd
    assert got.unicast_routes_to_delete == u_del
    assert got.mpls_routes_to_delete == m_del
    # by identity: the delta carries the new db's own objects
    for p, entry in got.unicast_routes_to_update.items():
        assert entry is new.unicast_routes[p]
    assert len(got.mpls_routes_to_update) == len(m_upd)
    for entry in got.mpls_routes_to_update:
        assert entry is new.mpls_routes[entry.label]

    # how often identity settled it
    table = len(new.unicast_routes) + len(new.mpls_routes)
    assert got.diff_identical == identical
    assert got.diff_identical + got.diff_compared == table

    # the diff leaves the installed db equal to what it was, key for
    # key and in the same order
    assert list(old.unicast_routes.items()) == list(before.unicast_routes.items())
    assert list(old.mpls_routes.items()) == list(before.mpls_routes.items())

    # installed + delta == new, and from then on every entry is the new
    # db's own object: a second diff of the same pair compares nothing
    old.update(got)
    assert old.unicast_routes == new.unicast_routes
    assert old.mpls_routes == new.mpls_routes
    for p, entry in new.unicast_routes.items():
        assert old.unicast_routes[p] is entry
    again = old.calculate_update(new)
    assert again.empty()
    assert (again.diff_identical, again.diff_compared) == (table, 0)


def test_an_equal_twin_is_adopted_and_nothing_else_is_touched():
    """Equal but distinct (a prefix re-derived to the same route): not in
    the delta, and the installed table takes the new object, so the pair
    goes to ``__eq__`` once and not in every later diff."""
    old = installed()
    new = same_objects(old)
    twin = unicast(3, area="1")
    label_twin = mpls(102)
    new.add_unicast_route(twin)
    new.add_mpls_route(label_twin)
    kept = {p: e for p, e in old.unicast_routes.items() if p != prefix(3)}
    delta = old.calculate_update(new)
    assert delta.empty() and delta.diff_compared == 2
    assert old.unicast_routes[prefix(3)] is twin
    assert old.mpls_routes[102] is label_twin
    for p, entry in kept.items():
        assert old.unicast_routes[p] is entry
    assert old.calculate_update(new).diff_compared == 0


def test_eq_is_not_called_on_an_entry_that_is_the_installed_object(monkeypatch):
    old = installed()
    new = same_objects(old)
    new.add_unicast_route(unicast(1, hops=(0,)))
    calls = []
    unicast_eq, mpls_eq = RibUnicastEntry.__eq__, RibMplsEntry.__eq__
    monkeypatch.setattr(
        RibUnicastEntry, "__eq__",
        lambda a, b: calls.append(a.prefix) or unicast_eq(a, b))
    monkeypatch.setattr(
        RibMplsEntry, "__eq__",
        lambda a, b: calls.append(a.label) or mpls_eq(a, b))
    delta = old.calculate_update(new)
    assert calls == [prefix(1)]
    assert list(delta.unicast_routes_to_update) == [prefix(1)]


def test_update_refuses_an_entry_filed_under_another_key():
    db = installed()
    delta = DecisionRouteUpdate(unicast_routes_to_update={prefix(0): unicast(1)})
    with pytest.raises(AssertionError):
        db.update(delta)
