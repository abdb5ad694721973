"""Served path ``pipeline_ksp2``: the ``pipeline`` driver for
configurations whose prefixes are ``KSP2_ED_ECMP`` over ``SR_MPLS``.

A configuration's ``served_path`` names a driver file, so this is where
the one thing ``fabric-1000-ksp2.adj-churn`` needs and the yardstick
lacks arrives: a plain reference that covers two ranks of edge-disjoint
paths and their label stacks. ``pipeline.Driver._verify`` asks
``chipbench/reference.py`` (SP_ECMP over IP, next hops without their
MPLS action) which routes the vantage must hold; here the same method
runs, every check of it, with ``chipbench/reference_ksp2.py`` answering
in its place, and the MPLS table, which ``_verify`` never looks at, is
held to the reference beside it: in Decision, in Fib and in the agent,
whose unicast table is compared entry for entry as well (``_verify``
counts it).

The node, the clock, the window, the drain, the host-backend replay of
the journal and every other rule of ``correct`` are ``pipeline.Driver``'s,
untouched. What a later ``benchmark`` issue should fold back: a
``reference`` attribute on ``pipeline.Driver`` that ``_verify`` reads,
and the MPLS comparison into ``_verify`` for every cell (each SP_ECMP
cell programs a node-label route beside every unicast one, unchecked).
"""

from __future__ import annotations

from types import SimpleNamespace

from chipbench import reference, reference_ksp2
from chipbench.served_paths import pipeline


class Driver(pipeline.Driver):
    def _verify(self) -> None:
        rec, gen = self.record, self.generator
        want = reference_ksp2.routes(gen.adj_dbs, gen.prefix_dbs, self.vantage)
        # ``reference``'s three names as ``pipeline.Driver._verify`` uses
        # them; the routes are those just computed (1015 Dijkstras)
        plain, pipeline.reference = pipeline.reference, SimpleNamespace(
            routes=lambda adj_dbs, prefix_dbs, vantage: want,
            routes_of=reference_ksp2.routes_of,
            relax_passes=reference.relax_passes,
        )
        try:
            super()._verify()
        finally:
            pipeline.reference = plain
        want_mpls = reference_ksp2.mpls_routes(gen.adj_dbs, self.vantage)
        live = self.decision.evb.call_and_wait(
            lambda: self.decision.route_db.to_route_db(self.vantage)
        )
        agent = SimpleNamespace(
            unicast_routes=self.agent.get_route_table_by_client(0),
            mpls_routes=self.agent.get_mpls_route_table_by_client(0),
        )
        wrong = []
        if reference_ksp2.routes_of(agent) != want:
            wrong.append("the agent's unicast table")
        for holder, route_db in (
            ("Decision", live), ("Fib", self.fib.get_route_db()),
            ("the agent", agent),
        ):
            if reference_ksp2.mpls_routes_of(route_db) != want_mpls:
                wrong.append(f"{holder}'s MPLS routes")
        if wrong:
            rec.problems.append(
                "the node-label routes of the final LSDB, or the unicast "
                "routes with their label stacks, differ in "
                + "; ".join(wrong)
            )
            rec.failed = rec.attempted
        rec.shapes["mpls_routes"] = len(want_mpls)
        # the destinations the engine holds paths for, and the passes a
        # masked solve, which starts cold, takes at the least
        rec.shapes["ksp2_dsts"] = sum(
            node != self.vantage for node in gen.prefix_dbs
        )
        rec.shapes["ksp2_passes"] = reference.relax_passes(
            gen.adj_dbs, [self.vantage]
        )
