"""What this process's own collector did in a window: passive, a
callback a collection. It tells a load generator that a full collection
held (~50 ms of a heap with jax imported) from one that the machine held.
Imports no JAX."""

from __future__ import annotations

import gc
import time


class GcWatch:
    """Every collection of this process inside the ``with``: ``events``
    holds (generation, seconds after the start, milliseconds it took)."""

    def __init__(self):
        self.events, self._t0, self._began = [], None, None

    def _callback(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._began = now
        elif self._began is not None:
            self.events.append((info["generation"], self._began - self._t0,
                                1e3 * (now - self._began)))
            self._began = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._callback)
        return False

    def summary(self, longest: int = 5) -> dict:
        by_gen = {}
        for gen, _, ms in self.events:
            n, total = by_gen.get(gen, (0, 0.0))
            by_gen[gen] = (n + 1, total + ms)
        return {
            "collections": {str(g): {"n": n, "ms": round(ms, 2)}
                            for g, (n, ms) in sorted(by_gen.items())},
            "longest_collections": [
                [g, round(at, 3), round(ms, 2)] for g, at, ms in
                sorted(self.events, key=lambda e: -e[2])[:longest]]}
