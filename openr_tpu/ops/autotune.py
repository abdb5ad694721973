"""Measured per-shape kernel autotuner.

Earlier on-chip captures flipped the jnp-vs-pallas min-plus winner with
shape and run on the same leg: neither implementation dominates, so
hardcoding either leaves time on the table somewhere. Instead of a
global default, ``impl="auto"`` resolves to a MEASURED winner per
``(platform, kernel, shape)`` key at build time: time each candidate on
synthetic operands of the real shape (one warmup for compile, best of
``reps`` timed runs) and memoize the winner for the life of the
process. Nothing is read from or written to disk: a winner is only as
good as the machine, the jax and the kernels it was measured with, and
the measurement costs a handful of dispatches (its compiles ride jax's
persistent compilation cache).

Resolution happens in the PUBLIC eager wrappers (``spf.
all_pairs_distances`` et al.) before jit entry — the winner is an
ordinary static ``impl`` argument by the time a trace sees it, so
"auto" never appears inside a compiled executable's key. A candidate
that raises is disqualified for that key: the exception is logged and
counted (``ops.autotune_disqualified``) — every selectable kernel is
proven to lower on the chip by ``chip_smoke.py``, so a disqualification
there is a defect, and the smoke fails on it. If every candidate raises
there is nothing to run and the last exception propagates.

The measurer is injectable (``Autotuner(measure=...)``) so tests drive
deterministic winner selection without timing noise.
"""

from __future__ import annotations

import functools
import logging
import time
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from openr_tpu.ops.spf import INF, KernelImpl
from openr_tpu.telemetry import get_registry

log = logging.getLogger(__name__)

# kernel family -> legal winner names: a winner recorded for one family
# can never arm a dispatch of another that shares the same shape key
_FAMILY_CANDIDATES = {
    "minplus": ("jnp", "pallas"),
    "grouped_minplus": ("jnp", "pallas", "pallas_t"),
}


def _default_measure(thunk: Callable[[], None], reps: int = 3) -> float:
    """Best-of-reps wall time in ms; one untimed warmup run eats the
    compile."""
    thunk()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        thunk()
        best = min(best, (time.perf_counter() - t0) * 1000.0)
    return best


class Autotuner:
    def __init__(self, measure: Optional[Callable] = None):
        self._measure = measure or _default_measure
        self._winners: Dict[str, str] = {}

    def record(self, kernel: str, shape_key: str, winner: str) -> None:
        """Adopt an EXTERNALLY measured winner (one that timed the real
        reconverge loop rather than a synthetic contraction) — memoized
        exactly like a ``pick`` result for the rest of the process."""
        assert kernel in _FAMILY_CANDIDATES, kernel
        assert winner in _FAMILY_CANDIDATES[kernel], (kernel, winner)
        platform = jax.devices()[0].platform
        self._winners[f"{platform}:{kernel}:{shape_key}"] = winner

    def pick(self, kernel: str, shape_key: str,
             candidates: Dict[str, Callable[[], None]]) -> str:
        """Winner name for (platform, kernel, shape): memoized, then
        measured."""
        platform = jax.devices()[0].platform
        key = f"{platform}:{kernel}:{shape_key}"
        got = self._winners.get(key)
        if got in candidates:
            return got
        reg = get_registry()
        timings: Dict[str, float] = {}
        last_exc: Optional[Exception] = None
        for name, thunk in candidates.items():
            try:
                timings[name] = self._measure(thunk)
            except Exception as exc:  # noqa: BLE001 - counted, logged
                last_exc = exc
                reg.counter_bump("ops.autotune_disqualified")
                log.exception(
                    "autotune: %s candidate %r disqualified at %s",
                    kernel, name, key,
                )
        if not timings:
            raise RuntimeError(
                f"autotune: every {kernel} candidate failed at {key}"
            ) from last_exc
        winner = min(timings, key=timings.get)
        self._winners[key] = winner
        reg.counter_bump("ops.autotune_measurements")
        return winner


_TUNER = Autotuner()


def get_autotuner() -> Autotuner:
    return _TUNER


def set_autotuner(tuner: Autotuner) -> None:
    global _TUNER
    _TUNER = tuner


@functools.partial(jax.jit, static_argnames=("impl",))
def _minplus_probe(a, b, impl):
    from openr_tpu.ops.spf import _minplus

    return _minplus(a, b, impl)


def resolve_minplus(shape: Tuple[int, ...], interpret: bool) -> KernelImpl:
    """Measured jnp-vs-pallas winner for the dense min-plus contraction
    at this [S, N] x [N, N] shape (spf's public wrappers call this when
    the impl is "auto", before jit entry). ``interpret`` is how the
    Pallas candidate runs, here and as the winner."""
    s = int(shape[0])
    n = int(shape[-1])

    def thunk(name):
        a = jnp.full((s, n), INF // 2, jnp.int32)
        b = jnp.full((n, n), INF // 2, jnp.int32)
        impl = KernelImpl(name, interpret)

        def run():
            _minplus_probe(a, b, impl).block_until_ready()

        return run

    winner = _TUNER.pick(
        "minplus", f"{s}x{n}",
        {"jnp": thunk("jnp"), "pallas": thunk("pallas")},
    )
    return KernelImpl(winner, interpret)


@functools.partial(jax.jit, static_argnames=("impl",))
def _grouped_probe(gath, w, impl):
    from openr_tpu.ops.spf_grouped import _contract

    return _contract(gath, w, impl)


def resolve_grouped(
    shape: Tuple[int, int, int, int], interpret: bool
) -> KernelImpl:
    """Measured winner for the grouped [B, G, S] x [G, S, R] block
    contraction; ``interpret`` as in resolve_minplus."""
    b, g, s, r = (int(x) for x in shape)

    def thunk(name):
        gath = jnp.full((b, g, s), INF // 2, jnp.int32)
        w = jnp.full((g, s, r), INF // 2, jnp.int32)
        impl = KernelImpl(name, interpret)

        def run():
            _grouped_probe(gath, w, impl).block_until_ready()

        return run

    winner = _TUNER.pick(
        "grouped_minplus", f"{b}x{g}x{s}x{r}",
        {"jnp": thunk("jnp"), "pallas": thunk("pallas"),
         "pallas_t": thunk("pallas_t")},
    )
    return KernelImpl(winner, interpret)
