"""The one general traffic generator. A mix is a data file.

``chipbench/traffic/<mix>.json`` holds parameters only::

    {"rate_per_s": 10,
     "arrivals": {"pattern": "steady"},
     "node_choice": {"law": "uniform"},
     "kinds": {"metric": 0.8, "flap": 0.2},
     "warmup": [["metric"], ["flap"], ["flap", "flap", "metric"], 2, 4],
     "drain_deadline_s": 20,
     "reaches_solver": true}

The loop is open: events are due on a schedule fixed before the window.
``arrivals`` names the pattern of that schedule (``steady``: one event
every 1/rate s) and ``node_choice`` the law by which an event picks its
node (``uniform``); an unknown value of either is an error, and a cell
that needs another brings it. ``kinds`` weighs the event kinds below.
``warmup`` is fired, and drained, before the window so that every shape
the window can need is compiled in set-up. It is a list of bursts, each
published back to back so that one rebuild window carries it whole, as
happens when an event arrives during a stall: a list of kinds, or a number of events to draw from the mix. What
a window compiles depends on how many rows its events change, so the
bursts are chosen to reach each bucket of changed rows the cell's rate
can produce. ``reaches_solver`` says whether the mix's events force a
device solve; ``correct`` holds the window to it either way. A mix that
does not may name a ``trace_probe``: one event kind that a traced run
publishes after the counted window, inside the profiler's session, so
that its trace shows the device path alive.

The whole schedule — due times, keys, versions and serialised payloads —
is drawn from ``--seed`` before the window opens, so the send loop does
nothing but sleep and publish. The event kinds are those of
``openr_tpu/load/generator.py`` (copied): an adjacency metric change, a
link flap (one side withdraws the adjacency; a later flap event restores
the oldest withdrawn one with probability 1/2), and a prefix update (a
node toggles one extra /128). The generator owns the evolving LSDB and
the per-key versions; after the last event its databases ARE the final
LSDB the plain reference solves.
"""

from __future__ import annotations

import random
from dataclasses import dataclass, replace
from typing import Callable, Dict, List, Optional, Tuple

from openr_tpu.types import (
    TTL_INFINITY,
    Adjacency,
    BinaryAddress,
    IpPrefix,
    Value,
)
from openr_tpu.utils import keys as keyutil
from openr_tpu.utils import wire

from chipbench.topology import Topology


@dataclass(frozen=True)
class Event:
    """One publication, ready to send."""

    kind: str
    key: str
    value: Value


def _value(version: int, node: str, payload: bytes) -> Value:
    return Value(
        version=version,
        originator_id=node,
        value=payload,
        ttl=TTL_INFINITY,
        hash=wire.generate_hash(version, node, payload),
    )


def extra_prefix(node_idx: int) -> IpPrefix:
    """The /128 a prefix event toggles: outside the loopbacks' fd00::/16."""
    val = (0xFD10 << 112) | node_idx
    return IpPrefix(BinaryAddress(addr=val.to_bytes(16, "big")), 128)


class Generator:
    """Seeded event stream over a mutable copy of a topology."""

    def __init__(self, topo: Topology, seed: int, mix: dict, spare: str):
        """``spare``: the vantage node. A flap never takes one of its own
        links, because that changes the solver's source-batch shape and
        compiles: a node losing its own uplink is another cell's traffic."""
        self._rng = random.Random(seed)
        self.adj_dbs = dict(topo.adj_dbs)
        self.prefix_dbs = dict(topo.prefix_dbs)
        self._nodes = sorted(self.adj_dbs)
        self._node_idx = {n: i for i, n in enumerate(self._nodes)}
        self._versions: Dict[str, int] = {}
        self._down: List[Tuple[str, Adjacency]] = []
        self._extra: set = set()
        self._spare = spare
        self._pick = self._chooser(mix.get("node_choice", {"law": "uniform"}))
        kinds = mix["kinds"]
        unknown = set(kinds) | {
            k for burst in mix.get("warmup", [])
            if isinstance(burst, list) for k in burst
        }
        unknown -= set(self._KINDS)
        if unknown:
            raise ValueError(f"unknown event kinds {sorted(unknown)}")
        self._kind_names = sorted(kinds)
        self._kind_weights = [kinds[k] for k in self._kind_names]

    # -- node choice ------------------------------------------------------

    def _chooser(self, choice: dict) -> Callable[[], str]:
        law = choice.get("law", "uniform")
        nodes = self._nodes
        if law == "uniform":
            return lambda: nodes[int(self._rng.random() * len(nodes)) % len(nodes)]
        raise ValueError(f"unknown node_choice law {law!r}")

    # -- the initial LSDB -------------------------------------------------

    def initial_key_vals(self) -> Dict[str, Value]:
        """Version 1 of every adjacency and prefix database, for one bulk
        load."""
        out: Dict[str, Value] = {}
        for name in self._nodes:
            for key, db in (
                (keyutil.adj_key(name), self.adj_dbs[name]),
                (keyutil.prefix_db_key(name), self.prefix_dbs[name]),
            ):
                self._versions[key] = 1
                out[key] = _value(1, name, wire.dumps(db))
        return out

    # -- events -----------------------------------------------------------

    def draw(self) -> Event:
        return self.event(
            self._rng.choices(self._kind_names, self._kind_weights)[0]
        )

    def event(self, kind: str) -> Event:
        return self._KINDS[kind](self)

    def burst(self, spec) -> List[Event]:
        """One warm-up burst: a list of kinds, or a count to draw."""
        if isinstance(spec, list):
            return [self.event(kind) for kind in spec]
        return [self.draw() for _ in range(int(spec))]

    def _emit(self, kind: str, key: str, node: str, db) -> Event:
        v = self._versions[key] = self._versions[key] + 1
        return Event(kind, key, _value(v, node, wire.dumps(db)))

    def _emit_adj(self, kind: str, node: str) -> Event:
        return self._emit(
            kind, keyutil.adj_key(node), node, self.adj_dbs[node]
        )

    def _pick_where(self, ok: Callable[[str], bool]) -> Optional[str]:
        """A node by the mix's law, redrawn until ``ok`` holds."""
        for _ in range(1000):
            node = self._pick()
            if ok(node):
                return node
        return None

    def _dead(self) -> set:
        """(node, neighbour) adjacencies whose far end is withdrawn. An
        event lands only on a link that is up both ways: a change to the
        surviving half of a dead link changes no route, so no rebuild
        would ever carry it."""
        return {(adj.other_node_name, node) for node, adj in self._down}

    def _metric(self) -> Event:
        dead = self._dead()

        def live(n: str) -> List[int]:
            return [
                i for i, a in enumerate(self.adj_dbs[n].adjacencies)
                if (n, a.other_node_name) not in dead
            ]

        node = self._pick_where(lambda n: bool(live(n)))
        db = self.adj_dbs[node]
        adjs = list(db.adjacencies)
        cand = live(node)
        i = cand[int(self._rng.random() * len(cand)) % len(cand)]
        adjs[i] = replace(adjs[i], metric=1 + (adjs[i].metric % 10))
        self.adj_dbs[node] = replace(db, adjacencies=tuple(adjs))
        return self._emit_adj("metric", node)

    def _flap(self) -> Event:
        if self._down and self._rng.random() < 0.5:
            node, adj = self._down.pop(0)
            db = self.adj_dbs[node]
            self.adj_dbs[node] = replace(
                db, adjacencies=db.adjacencies + (adj,)
            )
            return self._emit_adj("flap", node)

        dead = self._dead()

        def spared(n: str) -> List[int]:
            return [
                i for i, a in enumerate(self.adj_dbs[n].adjacencies)
                if self._spare not in (n, a.other_node_name)
                and (n, a.other_node_name) not in dead
            ]

        # a node keeps at least one adjacency: an unreachable originator
        # would make the result depend on timing
        node = self._pick_where(
            lambda n: len(self.adj_dbs[n].adjacencies) >= 2
            and bool(spared(n))
        )
        if node is None:
            return self._metric()
        db = self.adj_dbs[node]
        adjs = list(db.adjacencies)
        cand = spared(node)
        adj = adjs.pop(cand[int(self._rng.random() * len(cand)) % len(cand)])
        self.adj_dbs[node] = replace(db, adjacencies=tuple(adjs))
        self._down.append((node, adj))
        return self._emit_adj("flap", node)

    def _prefix(self) -> Event:
        node = self._pick()
        db = self.prefix_dbs[node]
        extra = extra_prefix(self._node_idx[node])
        if node in self._extra:
            self._extra.discard(node)
            entries = tuple(
                e for e in db.prefix_entries if e.prefix != extra
            )
        else:
            self._extra.add(node)
            entries = db.prefix_entries + (
                replace(db.prefix_entries[0], prefix=extra),
            )
        self.prefix_dbs[node] = replace(db, prefix_entries=entries)
        return self._emit(
            "prefix", keyutil.prefix_db_key(node), node, self.prefix_dbs[node]
        )

    _KINDS = {"metric": _metric, "flap": _flap, "prefix": _prefix}


def due_offsets(mix: dict, seconds: float) -> List[float]:
    """Seconds after the window opens at which each event is due."""
    rate = float(mix["rate_per_s"])
    arrivals = mix.get("arrivals", {"pattern": "steady"})
    pattern = arrivals.get("pattern", "steady")
    if pattern == "steady":
        return [i / rate for i in range(int(seconds * rate))]
    raise ValueError(f"unknown arrival pattern {pattern!r}")
