"""Served path ``pipeline_grid_ksp2``: the ``pipeline_ksp2`` driver on
upstream's grid, for ``grid-1000-ksp2``.

Nothing new is measured or compared here. Importing ``pipeline_grid``
registers the topology kind ``grid`` and the event kind ``node-metric``
(``grid-10000.drain-churn``'s); ``pipeline_ksp2.Driver`` brings the
reference that covers two ranks of edge-disjoint paths, their label
stacks and the node-label MPLS table (``chipbench/reference_ksp2.py``).

One rule is added, in set-up: **the deployment is the KSP2 engine on
the device, 60 hops from the vantage.** After the bulk load, before the
warm-up, the run stops with a reason unless the program built a KSP2
engine for the vantage's area (``decision.ksp2_cold_builds`` moved). A
program that answers this configuration from the host (a hop gate, a
host backend) produces the same routes, one Python Dijkstra per
destination and rebuild: that is another deployment, about a second an
event at this size, and the cell's 300 warm-up and window rebuilds of it
would be ground through for nothing. The run ends there instead, with a
non-zero exit code and nothing on the result line.
"""

from __future__ import annotations

from openr_tpu.telemetry import get_registry

from chipbench.served_paths import pipeline_grid  # noqa: F401 - registers
from chipbench.served_paths import pipeline_ksp2

ENGINE_BUILDS = "decision.ksp2_cold_builds"


class NoEngine(RuntimeError):
    """The bulk load left no KSP2 engine behind."""


class Driver(pipeline_ksp2.Driver):
    def set_up(self) -> None:
        self._engine_builds0 = get_registry().counter_get(ENGINE_BUILDS)
        self._engine_held = False
        self._published = 0
        super().set_up()

    def _publish(self, ev) -> None:
        self._published += 1
        super()._publish(ev)

    def _wait(self, pred, timeout_s: float, what: str) -> None:
        super()._wait(pred, timeout_s, what)
        # the first wait of a run is the bulk load's (first routes in
        # Fib); the warm-up's first burst is published after it returns.
        # ``pipeline.Driver.set_up`` has no named step there, so the
        # place is held to what it must look like: a reorder of set_up
        # stops the run instead of moving or skipping the rule
        if not self._engine_held:
            self._engine_held = True
            if not len(self.agent.unicast) or self._published:
                raise RuntimeError(
                    "pipeline.Driver.set_up no longer waits first for the "
                    "bulk load's routes in Fib "
                    f"({len(self.agent.unicast)} routes there, "
                    f"{self._published} events published): the engine rule "
                    "of pipeline_grid_ksp2 has lost its place"
                )
            self._hold_to_engine()

    def _hold_to_engine(self) -> None:
        built = (
            get_registry().counter_get(ENGINE_BUILDS) - self._engine_builds0
        )
        if built > 0:
            return
        router = self.config["router"]
        raise NoEngine(
            f"{self.config['name']}: the bulk load built no KSP2 engine "
            f"for {self.vantage}'s area ({ENGINE_BUILDS} did not move; "
            f"solver_backend={router['solver_backend']!r}): this program "
            "answers the configuration from the host, one Dijkstra per "
            "destination and rebuild, which is not the deployment this "
            "cell measures; stopped in set-up, before the warm-up"
        )
