"""Canonical binary wire codec for openr-tpu message types.

Plays the role the thrift binary protocol plays in the reference
(``openr/if/*.thrift`` generated serializers): every schema type in
``openr_tpu.types`` round-trips through a deterministic, compact binary
encoding. Determinism matters because the KvStore CRDT merge breaks ties on
the *serialized value bytes* (reference: openr/kvstore/KvStore.cpp:263
``mergeKeyValues`` comparing ``value_ref()->compare(...)``), so two nodes
encoding the same logical object must produce identical bytes.

Encoding (tag byte + payload):
  N             None
  T / F         bool
  I <zigzag>    int (varint, zigzag for negatives)
  S <len> utf8  str
  B <len> raw   bytes
  L <n> items   list / tuple
  D <n> k v...  dict, entries sorted by encoded key
  O <name> <n> fields   dataclass: class name + field values in field order

Decoding is schema-directed: ``loads(data, cls)`` rebuilds ``cls`` using its
dataclass field types (Optional / Tuple / List / Dict supported), so frozen
dataclasses and IntEnums come back as the right Python types.
"""

from __future__ import annotations

import dataclasses
import enum
import typing
from typing import (
    Any,
    Callable,
    Dict,
    Optional,
    Tuple,
    get_args,
    get_origin,
    get_type_hints,
)


#: per-class field-order memo for the encode path (field order is
#: static; ``dataclasses.fields`` rebuilds the tuple on every call)
_FIELDS_MEMO: Dict[type, tuple] = {}


def _encode_varint(n: int, out: bytearray) -> None:
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return


def _zigzag(n: int) -> int:
    return (n << 1) ^ (n >> 127) if n < 0 else (n << 1)


def _encode(obj: Any, out: bytearray) -> None:
    if obj is None:
        out.append(ord("N"))
    elif obj is True:
        out.append(ord("T"))
    elif obj is False:
        out.append(ord("F"))
    elif isinstance(obj, enum.IntEnum):
        out.append(ord("I"))
        _encode_varint(_zigzag(int(obj)), out)
    elif isinstance(obj, int):
        out.append(ord("I"))
        _encode_varint(_zigzag(obj), out)
    elif isinstance(obj, str):
        raw = obj.encode("utf-8")
        out.append(ord("S"))
        _encode_varint(len(raw), out)
        out.extend(raw)
    elif isinstance(obj, (bytes, bytearray)):
        out.append(ord("B"))
        _encode_varint(len(obj), out)
        out.extend(obj)
    elif isinstance(obj, (list, tuple)):
        out.append(ord("L"))
        _encode_varint(len(obj), out)
        for item in obj:
            _encode(item, out)
    elif isinstance(obj, (dict,)):
        entries = []
        for k, v in obj.items():
            kb = bytearray()
            _encode(k, kb)
            vb = bytearray()
            _encode(v, vb)
            entries.append((bytes(kb), bytes(vb)))
        entries.sort()
        out.append(ord("D"))
        _encode_varint(len(entries), out)
        for kb, vb in entries:
            out.extend(kb)
            out.extend(vb)
    elif isinstance(obj, (set, frozenset)):
        items = []
        for item in obj:
            ib = bytearray()
            _encode(item, ib)
            items.append(bytes(ib))
        items.sort()
        out.append(ord("L"))
        _encode_varint(len(items), out)
        for ib in items:
            out.extend(ib)
    elif dataclasses.is_dataclass(obj):
        out.append(ord("O"))
        name = type(obj).__name__.encode("utf-8")
        _encode_varint(len(name), out)
        out.extend(name)
        flds = _FIELDS_MEMO.get(type(obj))
        if flds is None:
            flds = dataclasses.fields(obj)
            _FIELDS_MEMO[type(obj)] = flds
        _encode_varint(len(flds), out)
        for f in flds:
            _encode(getattr(obj, f.name), out)
    else:
        raise TypeError(f"wire: cannot encode {type(obj)!r}")


def dumps(obj: Any) -> bytes:
    out = bytearray()
    _encode(obj, out)
    return bytes(out)


_N, _T, _F, _I, _S, _B, _L, _D, _O = b"NTFISBLDO"

#: a compiled decoder: ``(data, pos) -> (value, pos past it)``
Plan = Callable[[bytes, int], Tuple[Any, int]]

#: type annotation -> its plan, compiled on first use. What a value's
#: decode needs from its annotation (Optional stripped, the IntEnum
#: class, the element plan of ``Tuple[X, ...]``, a dataclass's field
#: plans) is static, so it is derived once and not per value
_PLANS: Dict[Any, Plan] = {}
#: dataclass -> its field plans (:class:`_ObjectPlan`), built at the
#: class's first decode
_OBJECT_PLANS: Dict[type, "_ObjectPlan"] = {}


def _varint(data: bytes, pos: int) -> Tuple[int, int]:
    b = data[pos]
    pos += 1
    if b < 0x80:
        return b, pos
    result = b & 0x7F
    shift = 7
    while True:
        b = data[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, pos
        shift += 7


def _strip_optional(tp: Any) -> Any:
    if get_origin(tp) is typing.Union:
        args = [a for a in get_args(tp) if a is not type(None)]
        if len(args) == 1:
            return args[0]
    return tp


class _ObjectPlan:
    """One dataclass's decode, derived once: the bytes its encoding
    opens with after the ``O`` tag (name length + name), its field
    names and each field's plan in field order, and for a field typed
    ``Tuple[X, ...]`` the plan of ``X`` (what :func:`loads_reusing`
    decodes a changed element with)."""

    __slots__ = ("cls", "header", "names", "plans", "elems")

    def __init__(self, cls: type):
        raw = cls.__name__.encode("utf-8")
        head = bytearray()
        _encode_varint(len(raw), head)
        self.cls = cls
        self.header = bytes(head) + raw
        # get_type_hints evaluates every stringified annotation
        # (PEP 563): once per class, never per value
        hints = get_type_hints(cls)
        self.names = tuple(f.name for f in dataclasses.fields(cls))
        types = [_strip_optional(hints.get(n, Any)) for n in self.names]
        self.plans = tuple(_plan(t) for t in types)
        self.elems = tuple(
            _plan(get_args(t)[0]) if _is_homogeneous_tuple(t) else None
            for t in types
        )

    def open(self, data: bytes, pos: int) -> Tuple[int, int]:
        """Past the ``O`` tag: check the class name, return the number
        of encoded fields and the offset of the first."""
        end = pos + len(self.header)
        if data[pos:end] == self.header:
            return _varint(data, end)
        n, pos = _varint(data, pos)
        name = data[pos : pos + n].decode("utf-8")
        nfields, pos = _varint(data, pos + n)
        if name != self.cls.__name__:
            raise TypeError(
                f"wire: expected {self.cls.__name__!r}, found {name!r}"
            )
        return nfields, pos

    def fields(
        self, data: bytes, pos: int, first: int, last: int, values: list
    ) -> int:
        """Decode encoded fields ``first`` .. ``last`` - 1 onto
        ``values``; one past the class's own fields is an unknown
        trailing field (forward compat): decoded untyped and dropped."""
        for plan in self.plans[first:last]:
            value, pos = plan(data, pos)
            values.append(value)
        for _ in range(max(first, len(self.plans)), last):
            _, pos = _decode_any(data, pos)
        return pos

    def decode(self, data: bytes, pos: int) -> Tuple[Any, int]:
        nfields, pos = self.open(data, pos)
        values: list = []
        pos = self.fields(data, pos, 0, nfields, values)
        return self.cls(*values), pos


def _object_plan(cls: type) -> _ObjectPlan:
    plan = _OBJECT_PLANS.get(cls)
    if plan is None:
        plan = _OBJECT_PLANS[cls] = _ObjectPlan(cls)
    return plan


def _is_homogeneous_tuple(tp: Any) -> bool:
    args = get_args(tp)
    return len(args) == 2 and args[1] is Ellipsis


def _compile(tp: Any) -> Plan:
    """The decoder of one annotation. The tag byte says what is on the
    wire; the annotation only says which Python type it comes back as
    (an IntEnum for an ``I``, a list / set / tuple for an ``L``, which
    dataclass for an ``O``), so every plan takes every tag."""
    tp = _strip_optional(tp)
    enum_cls = (
        tp if isinstance(tp, type) and issubclass(tp, enum.IntEnum) else None
    )
    is_object = dataclasses.is_dataclass(tp) and isinstance(tp, type)
    origin = get_origin(tp)
    args = get_args(tp)
    if origin in (list, set, frozenset):
        build = list if origin is list else set
        each, positional = _plan(args[0] if args else Any), ()
    elif _is_homogeneous_tuple(tp):
        build, each, positional = tuple, _plan(args[0]), ()
    else:  # untyped, or Tuple[X, Y]: by position, untyped past the end
        build, each = tuple, None
        positional = tuple(_plan(a) for a in args)
    key_plan, value_plan = (
        (_plan(args[0]), _plan(args[1])) if len(args) == 2 else (None, None)
    )

    def decode(data: bytes, pos: int) -> Tuple[Any, int]:
        tag = data[pos]
        pos += 1
        if tag == _I:
            n = data[pos]
            if n < 0x80:  # one-byte varint, inline
                pos += 1
            else:
                n, pos = _varint(data, pos)
            n = (n >> 1) ^ -(n & 1)  # un-zigzag
            return (n if enum_cls is None else enum_cls(n)), pos
        if tag == _S:
            n = data[pos]
            if n < 0x80:
                pos += 1
            else:
                n, pos = _varint(data, pos)
            end = pos + n
            return data[pos:end].decode("utf-8"), end
        if tag == _O:
            if is_object:
                return _object_plan(tp).decode(data, pos)
            n, pos = _varint(data, pos)
            name = data[pos : pos + n].decode("utf-8")
            _varint(data, pos + n)
            raise TypeError(f"wire: object {name!r} but target type is {tp!r}")
        if tag == _N:
            return None, pos
        if tag == _F:
            return False, pos
        if tag == _T:
            return True, pos
        if tag == _B:
            n, pos = _varint(data, pos)
            end = pos + n
            return bytes(data[pos:end]), end
        if tag == _L:
            n, pos = _varint(data, pos)
            items = []
            for i in range(n):
                plan = each or (
                    positional[i] if i < len(positional) else _decode_any
                )
                item, pos = plan(data, pos)
                items.append(item)
            return build(items), pos
        if tag == _D:
            n, pos = _varint(data, pos)
            out = {}
            for _ in range(n):
                key, pos = (key_plan or _decode_any)(data, pos)
                out[key], pos = (value_plan or _decode_any)(data, pos)
            return out, pos
        raise ValueError(f"wire: bad tag {tag!r} at {pos - 1}")

    return decode


def _plan(tp: Any) -> Plan:
    plan = _PLANS.get(tp)
    if plan is None:
        plan = _PLANS[tp] = _compile(tp)
    return plan


_decode_any = _plan(Any)


def _finish(data: bytes, pos: int) -> None:
    if pos != len(data):
        raise ValueError(f"wire: trailing bytes ({len(data) - pos})")


def loads(data: bytes, cls: Any) -> Any:
    obj, pos = _plan(cls)(data, 0)
    _finish(data, pos)
    return obj


class Decoded:
    """What :func:`loads_reusing` returns and takes back as ``prev``:
    the value's bytes (the caller's object, not a copy), the object
    decoded from them, the offsets at which the elements of the reused
    field begin (``bounds[i]`` .. ``bounds[i + 1]`` is element ``i``;
    empty where the field was not a sequence) and how many of those
    elements were taken from ``prev`` instead of decoded."""

    __slots__ = ("data", "obj", "bounds", "reused")

    def __init__(self, data: bytes, obj: Any, bounds: list, reused: int):
        self.data = data
        self.obj = obj
        self.bounds = bounds
        self.reused = reused

    @property
    def decoded(self) -> int:
        return max(0, len(self.bounds) - 1) - self.reused


def loads_reusing(
    data: bytes, cls: type, field: str, prev: Optional[Decoded] = None
) -> Decoded:
    """``loads(data, cls)`` for a dataclass whose ``field`` is a
    ``Tuple[X, ...]``, against the value last decoded for the same key:
    an element whose bytes are those of an element of ``prev`` IS that
    element (the object, not an equal one); every other element, every
    other field and the whole of a value with no ``prev`` go through the
    class's plan.

    Sound for any two byte strings: the encoding is self-delimiting, so
    where the bytes at the running offset equal a previous element's
    run, that run is what a decode from here would read, and it would
    build an equal object -- which is interchangeable with the previous
    one as long as ``X`` is frozen and holds only immutables. The walk
    keeps one cursor into ``prev``'s elements and on a miss also tries
    the element after it, so an element changed in place, one removed
    and one inserted each cost one decode; anything wilder decodes more,
    never wrongly."""
    plan = _object_plan(cls)
    at = plan.names.index(field)
    each = plan.elems[at]
    if each is None:
        raise TypeError(f"wire: {cls.__name__}.{field} is no Tuple[X, ...]")
    if data[0] != _O:  # the tag says what comes back, as in loads
        return Decoded(data, loads(data, cls), [], 0)
    nfields, pos = plan.open(data, 1)
    values: list = []
    pos = plan.fields(data, pos, 0, min(at, nfields), values)
    bounds: list = []
    reused = 0
    if at < nfields and data[pos] == _L:
        n, pos = _varint(data, pos + 1)
        old_data, old_items, old = b"", (), [0]
        if prev is not None and prev.bounds:
            old_data, old = prev.data, prev.bounds
            old_items = getattr(prev.obj, field)
        last = len(old) - 1
        j = 0
        items = []
        for _ in range(n):
            bounds.append(pos)
            # previous element j, else j + 1 (j is gone): neither -> j
            # changed in place or a new element stands before it; the
            # cursor stays and the next element settles which
            for k in (j, j + 1):
                if k < last:
                    start, end = old[k], old[k + 1]
                    step = pos + end - start
                    if data[pos:step] == old_data[start:end]:
                        items.append(old_items[k])
                        pos = step
                        j = k + 1
                        reused += 1
                        break
            else:
                item, pos = each(data, pos)
                items.append(item)
        bounds.append(pos)
        values.append(tuple(items))
        at += 1
    pos = plan.fields(data, pos, at, nfields, values)
    _finish(data, pos)
    return Decoded(data, cls(*values), bounds, reused)


def generate_hash(version: int, originator_id: str, value: bytes | None) -> int:
    """Stable hash over (version, originatorId, value) used by KvStore
    anti-entropy sync. reference: openr/common/Util.h generateHash.

    64-bit FNV-1a over the canonical encoding; signed-int64 result so it can
    ride in the same field the reference uses (thrift i64).
    """
    payload = dumps([version, originator_id, value])
    h = 0xCBF29CE484222325
    for b in payload:
        h ^= b
        h = (h * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
    # to signed 64-bit
    return h - (1 << 64) if h >= (1 << 63) else h
