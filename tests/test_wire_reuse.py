"""``wire.loads_reusing``: an ``AdjacencyDatabase`` decoded against the
value last decoded for its key equals ``wire.loads`` of the same bytes,
field for field, and takes from the previous value exactly the
adjacencies whose bytes stand -- under every edit an event makes to a
database (``chipbench/traffic.py`` ``_metric`` / ``_flap``, LinkMonitor
upstream) and a few no event makes. And the compiled plan behind
``wire.loads`` itself: every tag under every annotation, and each way a
value can be wrong. Counts and equalities only."""

from __future__ import annotations

import dataclasses
import enum
import random
from dataclasses import replace
from typing import Any, Dict, FrozenSet, List, Optional, Set, Tuple

import pytest

from openr_tpu.types import (
    Adjacency,
    AdjacencyDatabase,
    BinaryAddress,
    PerfEvent,
    PerfEvents,
)
from openr_tpu.utils import wire

SIZES = (1, 8, 84, 1000)


def _adjacency(i: int, metric: int = 10) -> Adjacency:
    return Adjacency(
        other_node_name=f"rsw-{i // 48}-{i % 48}",
        if_name=f"if-{i}",
        metric=metric,
        next_hop_v6=BinaryAddress.from_str(f"fe80::{i + 1:x}"),
        next_hop_v4=BinaryAddress.from_str(f"10.{i // 250}.0.{i % 250}"),
        adj_label=50000 + i,
        rtt=100 + i,
        timestamp=1700000000 + i,
        other_if_name=f"oif-{i}",
    )


def _database(n: int, metric: int = 10, **fields) -> AdjacencyDatabase:
    return AdjacencyDatabase(
        this_node_name="fsw-1-1",
        adjacencies=tuple(_adjacency(i, metric) for i in range(n)),
        node_label=1234,
        **fields,
    )


def _with(db, adjacencies):
    return replace(db, adjacencies=tuple(adjacencies))


def _recost(at, metric):
    """One adjacency's metric set, the others as they were; ``at`` is a
    share of the length so that one edit serves every size."""
    def edit(db):
        adjs = list(db.adjacencies)
        k = int(at * (len(adjs) - 1))
        adjs[k] = replace(adjs[k], metric=metric)
        return _with(db, adjs)
    return edit


def _remove(at):
    def edit(db):
        adjs = list(db.adjacencies)
        adjs.pop(int(at * (len(adjs) - 1)))
        return _with(db, adjs)
    return edit


def _insert(at):
    def edit(db):
        adjs = list(db.adjacencies)
        adjs.insert(int(round(at * len(adjs))), _adjacency(5000))
        return _with(db, adjs)
    return edit


def _swap(gap):
    def edit(db):
        adjs = list(db.adjacencies)
        i = (len(adjs) - 1) // 3
        k = min(i + gap, len(adjs) - 1)
        adjs[i], adjs[k] = adjs[k], adjs[i]
        return _with(db, adjs)
    return edit


def _events():
    return PerfEvents([PerfEvent("fsw-1-1", "ADJ_DB_UPDATED", 1700000001)])


# name -> (edit, adjacencies it leaves to take from the previous value
# as a function of how many that one held[, the metric every adjacency
# starts at])
EDITS = {
    "nothing": (lambda db: db, lambda n: n),
    "metric-up": (_recost(0.5, 11), lambda n: n - 1),
    "metric-down-first": (_recost(0.0, 3), lambda n: n - 1),
    "metric-up-last": (_recost(1.0, 9000), lambda n: n - 1),
    # across a varint's length: zigzag(63) is one byte, zigzag(64) two;
    # zigzag(8191) two, zigzag(8192) three -- every later element shifts
    "metric-63-to-64": (_recost(0.5, 64), lambda n: n - 1, 63),
    "metric-64-to-63": (_recost(0.5, 63), lambda n: n - 1, 64),
    "metric-8191-to-8192": (_recost(0.3, 8192), lambda n: n - 1, 8191),
    "metric-8192-to-8191": (_recost(0.3, 8191), lambda n: n - 1, 8192),
    "removed-front": (_remove(0.0), lambda n: n - 1),
    "removed-middle": (_remove(0.5), lambda n: n - 1),
    "removed-end": (_remove(1.0), lambda n: n - 1),
    "added-front": (_insert(0.0), lambda n: n),
    "added-middle": (_insert(0.5), lambda n: n),
    "added-end": (_insert(1.0), lambda n: n),
    # two neighbours swapped: the walk takes the second where it looks
    # for the first (one ahead) and has passed the first by then; two
    # far apart cost a decode each
    "swapped-neighbours": (_swap(1), lambda n: n - 1 if n > 1 else n),
    "swapped-apart": (
        _swap(3), lambda n: {1: 1, 8: 6, 84: 82, 1000: 998}[n]),
    "overloaded-flipped": (
        lambda db: replace(db, is_overloaded=True), lambda n: n),
    "node-label-flipped": (
        lambda db: replace(db, node_label=77), lambda n: n),
    "perf-events-added": (
        lambda db: replace(db, perf_events=_events()), lambda n: n),
    "perf-events-dropped": (
        lambda db: replace(db, perf_events=None), lambda n: n),
    "everything-changed": (
        lambda db: _with(db, [replace(a, metric=a.metric + 1)
                              for a in db.adjacencies]),
        lambda n: 0),
}


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("name", list(EDITS))
def test_a_decode_against_the_previous_value_is_loads(name, n):
    edit, stands, *metric = EDITS[name]
    before = _database(
        n, *metric,
        perf_events=_events() if name == "perf-events-dropped" else None)
    after = edit(before)
    old_bytes, new_bytes = wire.dumps(before), wire.dumps(after)

    prev = wire.loads_reusing(old_bytes, AdjacencyDatabase, "adjacencies")
    assert prev.obj == before
    assert (prev.reused, prev.decoded) == (0, n)
    assert prev.data is old_bytes

    got = wire.loads_reusing(
        new_bytes, AdjacencyDatabase, "adjacencies", prev)
    want = wire.loads(new_bytes, AdjacencyDatabase)
    assert got.obj == want == after
    for f in dataclasses.fields(AdjacencyDatabase):
        assert type(getattr(got.obj, f.name)) \
            is type(getattr(want, f.name)), f.name
    assert wire.dumps(got.obj) == new_bytes
    assert got.data is new_bytes

    total = len(after.adjacencies)
    assert (got.reused, got.decoded) == (stands(n), total - stands(n))
    # what was taken is the previous value's object, what was decoded is
    # not, and each element's run is where the bounds say
    was = {id(a) for a in prev.obj.adjacencies}
    assert sum(id(a) in was for a in got.obj.adjacencies) == got.reused
    assert len(got.bounds) == total + 1
    for i, adj in enumerate(got.obj.adjacencies):
        run = new_bytes[got.bounds[i]:got.bounds[i + 1]]
        assert run == wire.dumps(adj)
    # the header and perf_events are decoded fresh every time
    if after.perf_events is not None:
        assert got.obj.perf_events is not prev.obj.perf_events
    # and the result serves as the next previous value
    again = wire.loads_reusing(
        new_bytes, AdjacencyDatabase, "adjacencies", got)
    assert again.obj == after and again.reused == total


@pytest.mark.parametrize("seed", range(6))
def test_a_stream_of_wild_edits_never_decodes_wrongly(seed):
    """Edits no event makes (several at once, shuffles, wholesale
    replacement, an empty database): more is decoded, nothing is wrong,
    and only byte-equal elements are taken."""
    rng = random.Random(seed)
    db = _database(rng.choice((0, 5, 40)))
    prev = None
    fresh = 6000
    for _ in range(60):
        adjs = list(db.adjacencies)
        for _ in range(rng.randrange(4)):
            op = rng.randrange(5)
            if op == 0 and adjs:
                k = rng.randrange(len(adjs))
                adjs[k] = replace(
                    adjs[k], metric=rng.choice((1, 63, 64, 8191, 8192)))
            elif op == 1 and adjs:
                adjs.pop(rng.randrange(len(adjs)))
            elif op == 2:
                fresh += 1
                adjs.insert(rng.randrange(len(adjs) + 1), _adjacency(fresh))
            elif op == 3:
                rng.shuffle(adjs)
            elif op == 4 and rng.random() < 0.2:
                adjs = [_adjacency(fresh + i) for i in range(7)]
                fresh += 7
        db = replace(_with(db, adjs), is_overloaded=rng.random() < 0.3)
        data = wire.dumps(db)
        got = wire.loads_reusing(data, AdjacencyDatabase, "adjacencies", prev)
        assert got.obj == wire.loads(data, AdjacencyDatabase) == db
        assert got.reused + got.decoded == len(adjs)
        if prev is not None:
            was = {id(a) for a in prev.obj.adjacencies}
            assert sum(id(a) in was for a in got.obj.adjacencies) \
                == got.reused
        prev = got


@pytest.mark.parametrize("why, data, error", [
    ("empty", b"", IndexError),
    ("another-class", wire.dumps(_adjacency(0)), TypeError),
    ("truncated", wire.dumps(_database(8))[:200], (IndexError, ValueError)),
    ("trailing", wire.dumps(_database(8)) + b"N", ValueError),
    ("bad-tag-in-element",
     wire.dumps(_database(8)).replace(b"S\x04if-3", b"?\x04if-3"),
     ValueError),
])
def test_a_bad_value_raises_as_loads_does(why, data, error):
    prev = wire.loads_reusing(
        wire.dumps(_database(8)), AdjacencyDatabase, "adjacencies")
    with pytest.raises(error):
        wire.loads(data, AdjacencyDatabase)
    for against in (None, prev):
        with pytest.raises(error):
            wire.loads_reusing(
                data, AdjacencyDatabase, "adjacencies", against)
    assert prev.obj == _database(8)


# -- the compiled plan behind wire.loads ---------------------------------


class Colour(enum.IntEnum):
    RED = 1
    BLUE = 2


@dataclasses.dataclass(frozen=True)
class Inner:
    a: int = 0
    b: Optional[str] = None


@dataclasses.dataclass
class Outer:
    name: str
    colour: Colour = Colour.RED
    inner: Optional[Inner] = None
    many: Tuple[Inner, ...] = ()
    numbers: List[int] = dataclasses.field(default_factory=list)
    words: Set[str] = dataclasses.field(default_factory=set)
    frozen: FrozenSet[int] = frozenset()
    table: Dict[str, Inner] = dataclasses.field(default_factory=dict)
    pair: Tuple[int, str] = (0, "")
    anything: Any = None
    raw: bytes = b""
    flag: bool = False


@dataclasses.dataclass
class OuterNext:
    """``Outer`` as a later version would send it: one field more."""
    name: str
    colour: Colour = Colour.RED
    inner: Optional[Inner] = None
    many: Tuple[Inner, ...] = ()
    numbers: List[int] = dataclasses.field(default_factory=list)
    words: Set[str] = dataclasses.field(default_factory=set)
    frozen: FrozenSet[int] = frozenset()
    table: Dict[str, Inner] = dataclasses.field(default_factory=dict)
    pair: Tuple[int, str] = (0, "")
    anything: Any = None
    raw: bytes = b""
    flag: bool = False
    later: Dict[str, Tuple[int, ...]] = dataclasses.field(
        default_factory=lambda: {"later": (1, 2)})


OuterNext.__name__ = "Outer"

FULL = Outer(
    "n", Colour.BLUE, Inner(-3, "x"), (Inner(1), Inner(2, "y")),
    [0, -1, 63, 64, 8191, 8192, 1 << 40], {"b", "a"}, frozenset({3, 1}),
    {"k": Inner(5, None), "j": Inner()}, (7, "z"),
    (1, "s", None, {"q": b"\x00"}), b"\x00\xff", True,
)


@pytest.mark.parametrize("field, kind", [
    ("name", str), ("colour", Colour), ("inner", Inner), ("many", tuple),
    ("numbers", list), ("words", set), ("frozen", set), ("table", dict),
    ("pair", tuple), ("anything", tuple), ("raw", bytes), ("flag", bool),
])
def test_each_annotation_comes_back_as_its_type(field, kind):
    back = wire.loads(wire.dumps(FULL), Outer)
    assert type(getattr(back, field)) is kind
    assert getattr(back, field) == getattr(FULL, field)
    assert all(type(i) is Inner for i in back.many)
    assert all(type(v) is Inner for v in back.table.values())


@pytest.mark.parametrize("value, annotation, want", [
    (None, Optional[Inner], None),
    (None, int, None),
    (Inner(1, "a"), Optional[Inner], Inner(1, "a")),
    (2, Colour, Colour.BLUE),
    (2, Optional[Colour], Colour.BLUE),
    (2, Any, 2),
    (-(1 << 70), int, -(1 << 70)),
    ([1, 2], Tuple[int, ...], (1, 2)),
    ([1, 2], List[int], [1, 2]),
    ([1, 2], list, (1, 2)),
    ([1, 2], Set[int], {1, 2}),
    ([1, 2], FrozenSet[int], {1, 2}),
    ([1, "a", 3], Tuple[int, str], (1, "a", 3)),
    ([2, 1], Tuple[Colour, ...], (Colour.BLUE, Colour.RED)),
    ({"a": 2}, Dict[str, Colour], {"a": Colour.BLUE}),
    ({"a": [1]}, Dict[str, Tuple[int, ...]], {"a": (1,)}),
    ({"a": 1}, Any, {"a": 1}),
    ("s", int, "s"),
    (b"\x80", str, b"\x80"),
    (True, int, True),
])
def test_the_tag_says_what_is_on_the_wire_the_annotation_what_type(
        value, annotation, want):
    got = wire.loads(wire.dumps(value), annotation)
    assert got == want and type(got) is type(want)


def _named(name: str, nfields: int, body: bytes = b"") -> bytes:
    raw = name.encode()
    return b"O" + bytes([len(raw)]) + raw + bytes([nfields]) + body


@pytest.mark.parametrize("why, data, cls, error, says", [
    ("wrong-class-name", wire.dumps(Inner(1)), Outer, TypeError,
     "expected 'Outer', found 'Inner'"),
    ("wrong-class-nested", wire.dumps(Outer("n", inner=Inner())).replace(
        b"Inner", b"Outer"), Outer, TypeError,
     "expected 'Inner', found 'Outer'"),
    ("object-for-a-scalar", wire.dumps(Inner(1)), int, TypeError,
     "object 'Inner' but target type is"),
    ("object-untyped", wire.dumps(Inner(1)), Any, TypeError,
     "object 'Inner' but target type is"),
    ("bad-tag", b"?", Any, ValueError, "bad tag 63 at 0"),
    ("bad-tag-inside", _named("Inner", 2, b"I\x02Z"), Inner, ValueError,
     "bad tag 90 at 10"),
    ("trailing-bytes", wire.dumps(Inner(1)) + b"NN", Inner, ValueError,
     "trailing bytes (2)"),
    ("trailing-after-scalar", b"I\x02T", int, ValueError,
     "trailing bytes (1)"),
    ("cut-in-a-string", wire.dumps("abcdef")[:4], str, ValueError,
     "trailing bytes (-4)"),
    ("cut-in-a-varint", b"I\x80", int, IndexError, "index out of range"),
    ("cut-before-a-field", _named("Inner", 2, b"I\x02"), Inner, IndexError,
     "index out of range"),
    ("unknown-enum-value", wire.dumps(7), Colour, ValueError,
     "7 is not a valid Colour"),
    ("bad-utf8", b"S\x01\xff", str, UnicodeDecodeError, "invalid start byte"),
    ("missing-required-field", _named("Outer", 0), Outer, TypeError,
     "missing 1 required positional argument"),
])
def test_each_way_a_value_is_wrong(why, data, cls, error, says):
    with pytest.raises(error) as caught:
        wire.loads(data, cls)
    assert type(caught.value) is error
    assert says in str(caught.value)


def test_unknown_trailing_fields_are_ignored():
    """A newer peer's extra field, whatever it holds, is decoded past
    and dropped; a value with fewer fields takes the defaults."""
    newer = OuterNext(**{f.name: getattr(FULL, f.name)
                         for f in dataclasses.fields(FULL)})
    assert wire.loads(wire.dumps(newer), Outer) == FULL
    # ... unless it holds an object: untyped, no class to build it as
    newer.later = {"later": Inner()}
    with pytest.raises(TypeError, match="object 'Inner' but target type"):
        wire.loads(wire.dumps(newer), Outer)
    older = _named("Inner", 1, b"I\x0a")
    assert wire.loads(older, Inner) == Inner(5, None)
    # a non-canonical (padded) name length still names the class
    padded = b"O\x85\x00Inner\x01I\x0a"
    assert wire.loads(padded, Inner) == Inner(5, None)


def test_one_decoder():
    """``wire`` holds the compiled plan and nothing beside it."""
    for gone in ("_Reader", "_decode", "_class_memo", "_CLASS_MEMO"):
        assert not hasattr(wire, gone), gone
    assert wire._plan(Inner) is wire._plan(Inner)
    assert wire._plan(Optional[Inner]) is not None
    assert wire._object_plan(Inner).names == ("a", "b")


def test_a_value_whose_field_is_no_sequence_is_decoded_whole():
    """The reused field encoded as ``N``, or the value no object at all
    (no peer sends either, the codec admits both): what ``loads`` gives,
    no bounds, and nothing to reuse the next time either."""
    data = wire.dumps(dataclasses.replace(FULL, many=None))
    got = wire.loads_reusing(data, Outer, "many")
    assert got.obj == wire.loads(data, Outer) and got.obj.many is None
    assert (got.bounds, got.reused, got.decoded) == ([], 0, 0)
    after = wire.loads_reusing(wire.dumps(FULL), Outer, "many", got)
    assert after.obj == FULL and (after.reused, after.decoded) == (0, 2)
    got = wire.loads_reusing(wire.dumps([1, 2]), Outer, "many", after)
    assert got.obj == wire.loads(wire.dumps([1, 2]), Outer) == (1, 2)
    assert (got.bounds, got.reused) == ([], 0)
    # a value cut short of the field: the defaults, as loads gives them
    short = _named("Outer", 1, b"S\x01n")
    got = wire.loads_reusing(short, Outer, "many", after)
    assert got.obj == wire.loads(short, Outer) == Outer("n")
    with pytest.raises(TypeError, match="no Tuple"):
        wire.loads_reusing(wire.dumps(FULL), Outer, "numbers")
