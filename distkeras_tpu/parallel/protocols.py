"""Async optimization protocols as pure update rules.

The heart of dist-keras parity. The reference implements each protocol as a
(Worker subclass, ParameterServer subclass) pair exchanging pickled weights
over TCP (``distkeras/workers.py`` § ``DOWNPOURWorker``/``ADAGWorker``/
``AEASGDWorker``/``EAMSGDWorker``/``DynSGDWorker`` +
``distkeras/parameter_servers.py`` § ``DeltaParameterServer``/
``ADAGParameterServer``/``DynSGDParameterServer``). Here each protocol is a
small strategy object made of **pure PyTree functions**:

- ``server_commit(center, num_updates, payload) -> (center, num_updates)``
  — the single-owner PS state transition (no locks needed by construction);
- ``worker_begin(client, params)`` / ``worker_window(params, carry, client)``
  — the per-``communication_window`` exchange run by each worker between
  stretches of jitted local train steps.

Protocol semantics preserved from the reference:

DOWNPOUR   worker pushes the weight delta accumulated over the window, then
           pulls the fresh center; server applies ``center += delta``.
ADAG       same worker; server normalizes: ``center += delta / num_workers``
           (accumulated-gradient normalization — the reference author's own
           protocol; the 1/n scaling tames asynchronous staleness).
AEASGD     elastic averaging: worker computes the elastic force
           ``e = rho * lr * (local - center)``, applies ``local -= e`` and
           commits ``e``; server applies ``center += e``.
EAMSGD     AEASGD plus Nesterov-style momentum on the local update.
DynSGD     staleness-aware: pull returns ``(center, num_updates)``; commit
           carries the puller's ``last_update``; server applies
           ``center += delta / (staleness + 1)`` with
           ``staleness = num_updates - last_update`` and bumps the counter
           (reference ``DynSGDParameterServer.handle_commit`` semantics,
           SURVEY §3.3).
"""

from __future__ import annotations

import collections
import dataclasses
import uuid
from typing import Any

import jax
import ml_dtypes
import numpy as np
import optax

from distkeras_tpu.utils.pytree import (
    pytree_add,
    pytree_l2,
    pytree_scale,
    pytree_sub,
    pytree_to_host,
)

__all__ = [
    "AsyncProtocol",
    "DOWNPOURProtocol",
    "ADAGProtocol",
    "AEASGDProtocol",
    "EAMSGDProtocol",
    "DynSGDProtocol",
]

PyTree = Any

# High bit of the fused-exchange reply counter: "the PS lost your mirror —
# re-bootstrap with full params" (fits the wire's u64 counter field).
_REBOOTSTRAP = 1 << 63


@dataclasses.dataclass
class WorkerCarry:
    """Per-worker protocol bookkeeping between windows."""

    window_start: PyTree | None = None  # params snapshot at window start
    last_update: int = 0  # DynSGD: server counter seen at last pull
    worker_id: str = ""  # elastic family: keys the server-side mirror
    mirror: PyTree | None = None  # elastic family: shared worker/PS mirror


class AsyncProtocol:
    """Base strategy. Subclasses override the three hooks below."""

    name = "async"

    def __init__(self, communication_window: int = 5):
        self.communication_window = int(communication_window)

    # -- server side (runs inside the single-owner PS loop) ------------------

    def server_commit(
        self, center: PyTree, num_updates: int, payload: dict, num_workers: int
    ) -> tuple[PyTree, int]:
        raise NotImplementedError

    def server_commit_pull(
        self, center: PyTree, num_updates: int, payload: dict, num_workers: int
    ) -> tuple[PyTree, int, tuple[PyTree, int]]:
        """Fused exchange: apply the commit and produce the reply in one PS
        transition. Returns ``(new_center, new_num_updates, reply)`` where
        ``reply = (tree, counter)`` is what the committing worker receives —
        by default the fresh post-commit center, restoring the reference's
        one-round-trip-per-window cadence (``distkeras/workers.py`` §
        ``NetworkWorker`` commit+pull pair collapsed into one exchange)."""
        new_center, new_n = self.server_commit(center, num_updates, payload, num_workers)
        return new_center, new_n, (new_center, new_n)

    def server_duplicate_reply(
        self, center: PyTree, num_updates: int, payload: dict
    ) -> tuple[PyTree, int]:
        """Reply for a fused exchange whose commit was already applied (a
        retried ``commit_pull`` caught by the PS dedupe window): nothing is
        re-applied, but the worker still needs an answer."""
        return center, num_updates

    # -- health telemetry ----------------------------------------------------

    def commit_stats(
        self, center: PyTree, num_updates: int, payload: dict,
        num_workers: int
    ) -> dict:
        """Health accounting for ONE commit, evaluated against the
        PRE-commit PS state (the staleness and divergence definitions
        need the counter/center the committer raced against). Called by
        the PS loop when a :class:`~distkeras_tpu.telemetry.
        training_health.TrainingHealth` is attached; one O(n_params)
        host pass, same order as the commit apply itself. Returns:

        - ``staleness`` — ``num_updates - last_update`` (the quantity
          DynSGD damps by; 0 for a perfectly fresh pull);
        - ``damping`` — the scalar mass factor this protocol applies to
          the update (goodput = damped / committed mass);
        - ``update_norm`` — L2 of the committed update, when the
          payload carries one;
        - ``divergence`` — elastic family only: ``||local - center||``.
        """
        out: dict = {"damping": 1.0}
        last = payload.get("last_update")
        if last is not None:
            out["staleness"] = max(0, num_updates - int(last))
        if "delta" in payload:
            out["update_norm"] = pytree_l2(payload["delta"])
        return out

    # -- worker side ---------------------------------------------------------

    def local_optimizer(
        self, base: optax.GradientTransformation
    ) -> optax.GradientTransformation:
        """Hook for protocols that modify the local update rule (EAMSGD)."""
        return base

    def worker_begin(self, client, params: PyTree) -> tuple[PyTree, WorkerCarry]:
        """Initial pull: start every worker from the shared center."""
        center, num_updates = client.pull()
        return center, WorkerCarry(window_start=center, last_update=num_updates)

    def worker_window(
        self, params: PyTree, carry: WorkerCarry, client
    ) -> tuple[PyTree, WorkerCarry]:
        raise NotImplementedError


def _device_delta(params, base):
    """Whole-tree ``params - base`` as one compiled dispatch when params
    live on device (the per-window worker delta); host numpy trees keep the
    numpy path (the PS loop must not bounce through the accelerator)."""
    leaves = jax.tree.leaves(params)
    if leaves and isinstance(leaves[0], jax.Array):
        global _delta_jit
        if _delta_jit is None:
            _delta_jit = jax.jit(
                lambda p, b: jax.tree.map(lambda x, y: x - y, p, b)
            )
        return _delta_jit(params, base)
    return pytree_sub(params, base)


_delta_jit = None


def _wire_bf16(tree):
    """Cast wide float leaves to bfloat16 for the wire (half of f32 bytes);
    everything else ships unchanged. Exact for trees already in bf16.
    Host-side ml_dtypes cast (round-to-nearest-even, same as XLA) — the PS
    loop must never bounce trees through a device (ps.py design note)."""

    def cast(x):
        a = np.asarray(x)
        if a.dtype.kind == "f" and a.dtype.itemsize > 2:
            return a.astype(ml_dtypes.bfloat16)
        return a

    return jax.tree.map(cast, tree)


def _wire_f32(tree):
    """Upcast bf16 wire leaves back to float32 (exact — bf16 is a prefix of
    f32); other leaves pass through."""

    def up(x):
        a = np.asarray(x)
        if a.dtype.name == "bfloat16":
            return a.astype(np.float32)
        return a

    return jax.tree.map(up, tree)


class _DeltaWindowMixin:
    """Commit accumulated window delta and receive the fresh center in one
    fused exchange — the DOWNPOUR/ADAG/DynSGD worker cadence (SURVEY §3.1 hot
    loop) at the reference's one-RTT-per-window cost. Falls back to separate
    commit + pull round trips for clients without ``commit_pull``."""

    def worker_window(self, params, carry, client):
        delta = _device_delta(params, carry.window_start)
        payload = {"delta": delta, "last_update": carry.last_update}
        fused = getattr(client, "commit_pull", None)
        if fused is not None:
            center, num_updates = fused(payload)
        else:
            client.commit(payload)
            center, num_updates = client.pull()
        return center, WorkerCarry(window_start=center, last_update=num_updates)


class DOWNPOURProtocol(_DeltaWindowMixin, AsyncProtocol):
    """Dean et al. Downpour SGD (reference ``DOWNPOUR`` trainer +
    ``DeltaParameterServer``)."""

    name = "downpour"

    def server_commit(self, center, num_updates, payload, num_workers):
        return pytree_add(center, payload["delta"]), num_updates + 1


class ADAGProtocol(_DeltaWindowMixin, AsyncProtocol):
    """Accumulated-gradient normalization (reference ``ADAG`` trainer +
    ``ADAGParameterServer``): commit scaled by 1/num_workers."""

    name = "adag"

    def __init__(self, communication_window: int = 12):
        super().__init__(communication_window)

    def server_commit(self, center, num_updates, payload, num_workers):
        scaled = pytree_scale(payload["delta"], 1.0 / max(1, num_workers))
        return pytree_add(center, scaled), num_updates + 1

    def commit_stats(self, center, num_updates, payload, num_workers):
        out = super().commit_stats(center, num_updates, payload, num_workers)
        out["damping"] = 1.0 / max(1, num_workers)
        return out


class AEASGDProtocol(AsyncProtocol):
    """Asynchronous Elastic Averaging SGD (Zhang et al.; reference ``AEASGD``
    trainer). ``rho`` and ``learning_rate`` follow the reference kwargs.

    Wire format of the fused exchange (one RTT per window, like the
    reference's pull→compute→commit pair): the first window of each worker
    bootstraps by shipping its full-precision ``local`` params; every later
    window ships only ``bf16(local - mirror)``, where ``mirror`` is a
    per-worker tree maintained **bit-identically** on both sides (both
    advance it as ``mirror + f32(diff) - f32(e)`` from the very bytes that
    crossed the wire). The PS reconstructs ``local ≈ mirror + diff``,
    computes the elastic force against the center *it* owns, applies
    ``center += e``, and replies ``bf16(e)``. Steady-state wire cost is
    2 bytes/param each way vs 4+4 for raw f32 — a 2× reduction — and the
    bf16 rounding only ever touches *differences* of nearby trees (the
    window's local progress, and the force ``α·(local - center)``), never
    absolute weights, so the truncation is benign the same way bf16 commit
    deltas are (see :class:`distkeras_tpu.parallel.ha.CompressingClient`).
    PS-side cost: up to ``max(2*num_workers, 4)`` mirror trees (stored in
    ``mirror_dtype``, default bf16 — the mirror's own rounding cancels out
    of the reconstruction, see ``_round_mirror``) plus up to
    ``max(4*num_workers, 8)`` recorded replies (f32 model-sized worst case
    after a bootstrap exchange, bf16 force-sized in steady state) —
    worst-case budget ``num_workers * (2*2 + 4*4) = 20 bytes/param``
    (:meth:`host_state_budget`, logged at service start and asserted in
    ``tests/test_protocols.py``).
    """

    name = "aeasgd"

    def __init__(
        self,
        communication_window: int = 32,
        rho: float = 5.0,
        learning_rate: float = 0.1,
        mirror_dtype: str = "bfloat16",
    ):
        super().__init__(communication_window)
        self.rho = float(rho)
        self.learning_rate = float(learning_rate)
        # Mirror storage precision. The wire is already bf16-rounded both
        # directions, and the mirror's own rounding cancels out of the
        # reconstruction (local_est - local = bf16(δ) - δ regardless of the
        # mirror's absolute error), so bf16 halves the PS's dominant host
        # cost. Accuracy note: δ = local - mirror now carries the mirror's
        # PARAMETER-scale bf16 residual, so the per-window reconstruction
        # error grows by roughly |param|·2^-18 on top of the |update|·2^-9
        # wire rounding a float32 mirror already had — benign for elastic
        # averaging, but not free. Both sides round with the SAME
        # round-to-nearest-even cast in the same expression order, keeping
        # the mirrors bit-identical. "float32" restores the old behavior.
        if mirror_dtype not in ("bfloat16", "float32"):
            raise ValueError(f"mirror_dtype must be bfloat16|float32, got {mirror_dtype!r}")
        self.mirror_dtype = mirror_dtype
        # Server-side per-worker state, touched only by the single-owner PS
        # loop: the shared mirror tree and the last fused reply (replayed
        # verbatim for a deduped retry — exactly-once answers). Each is
        # LRU-bounded INDEPENDENTLY (see _set_mirror/_set_reply): worker ids
        # are per-incarnation, so restarts would otherwise leak a
        # model-sized tree each. Evicting a live worker's mirror is safe —
        # it just re-bootstraps next window — but its reply must outlive the
        # mirror: if the reply died with the mirror, a lost-reply retry
        # arriving after eviction would be told "nothing applied" when the
        # commit DID move the center, and the worker would skip its side of
        # the elastic pull (asymmetric apply). A reply is superseded by the
        # worker's next successful exchange; only 2×num_workers dead
        # incarnations can age one out, so the asymmetric window survives
        # only a PS restart (documented as accepted elastic-averaging noise
        # — the next bootstrap re-centers the pair).
        self._mirrors: "collections.OrderedDict[str, PyTree]" = (
            collections.OrderedDict()
        )
        self._last_reply: "collections.OrderedDict[str, tuple]" = (
            collections.OrderedDict()
        )
        # Consume-once memo handing commit_stats' local-params
        # reconstruction to the server_commit_pull that immediately
        # follows it in the single-owner PS loop (see _local_of).
        self._local_memo: tuple | None = None

    def server_commit(self, center, num_updates, payload, num_workers):
        return pytree_add(center, payload["delta"]), num_updates + 1

    def _elastic(self, local, center):
        alpha = self.rho * self.learning_rate
        return pytree_scale(pytree_sub(local, center), alpha)

    def _round_mirror(self, tree):
        """Round a freshly-advanced mirror to the storage dtype — the ONE
        cast both sides share; any asymmetry here would split the mirrors."""
        return _wire_bf16(tree) if self.mirror_dtype == "bfloat16" else tree

    def _local_of(self, payload):
        """Reconstruct the committing worker's local params (bootstrap
        ``local``, or steady-state mirror + ``elastic_diff``); None when
        the mirror is gone and the diff alone cannot. One O(n_params)
        host pass, shared between commit_stats and the
        server_commit_pull that immediately follows it in the
        single-owner PS loop via a consume-once memo — health telemetry
        must not double the loop's dominant host cost."""
        memo, self._local_memo = self._local_memo, None
        if memo is not None and memo[0] is payload:
            return memo[1]
        if "elastic_diff" in payload:
            wid = payload.get("worker_id")
            if wid not in self._mirrors:
                return None
            return pytree_add(
                _wire_f32(self._mirrors[wid]),
                _wire_f32(payload["elastic_diff"]))
        if "local" in payload:
            return pytree_to_host(payload["local"])
        return None

    def commit_stats(self, center, num_updates, payload, num_workers):
        """Elastic health: ``divergence = ||local - center||_2`` against
        the pre-commit center (the quantity elastic averaging is built
        to shrink — its growth IS the diverging-run signal), and the
        applied force's norm ``alpha * divergence`` as the update mass.
        The local-params reconstruction is memoized for the
        server_commit_pull about to apply this same payload."""
        out = super().commit_stats(center, num_updates, payload, num_workers)
        local = self._local_of(payload)
        if local is not None:
            self._local_memo = (payload, local)
            divergence = pytree_l2(pytree_sub(local, center))
            out["divergence"] = divergence
            out["update_norm"] = self.rho * self.learning_rate * divergence
        return out

    def host_state_budget(self, n_params: int, num_workers: int) -> int:
        """Worst-case PS host bytes for this protocol's per-worker state:
        ``max(2N, 4)`` mirrors (mirror_dtype) + ``max(4N, 8)`` recorded
        replies (f32 model-sized worst case — a bootstrap reply; steady
        state is bf16 force-sized). Logged at service start."""
        mirror_bytes = 2 if self.mirror_dtype == "bfloat16" else 4
        mirrors = max(2 * int(num_workers), 4) * mirror_bytes * n_params
        replies = max(4 * int(num_workers), 8) * 4 * n_params
        return mirrors + replies

    def server_commit_pull(self, center, num_updates, payload, num_workers):
        # Fused elastic exchange (see class docstring). Two request shapes:
        # bootstrap ``local`` (full precision) and steady-state
        # ``elastic_diff`` (bf16 delta against the shared mirror).
        wid = payload.get("worker_id")
        if "elastic_diff" in payload:
            local_est = self._local_of(payload)
            if local_est is None:
                # Mirror lost (PS restarted from checkpoint, or LRU-evicted):
                # the diff alone cannot reconstruct the worker's local
                # params. Apply nothing; the flagged counter tells the
                # worker to re-bootstrap with full params next window.
                # Nothing is recorded: a deduped retry reconstructs the same
                # flagged zero reply from its own payload in
                # server_duplicate_reply (storing it here would leak a
                # model-sized tree per dead incarnation — the wid is not in
                # _mirrors, so _set_mirror's eviction can never reach it).
                zero = pytree_scale(payload["elastic_diff"], 0.0)  # stays bf16: unread
                return center, num_updates, (zero, _REBOOTSTRAP | num_updates)
            e_wire = _wire_bf16(self._elastic(local_est, center))
            e = _wire_f32(e_wire)
            self._set_mirror(
                wid, self._round_mirror(pytree_sub(local_est, e)), num_workers
            )
            reply = (e_wire, num_updates)
            self._set_reply(wid, reply, num_workers)
            return pytree_add(center, e), num_updates + 1, reply
        if "local" in payload:
            local = self._local_of(payload)
            e = self._elastic(local, center)
            reply = (e, num_updates)
            if wid is not None:
                self._set_mirror(
                    wid, self._round_mirror(pytree_sub(local, e)), num_workers
                )
                self._set_reply(wid, reply, num_workers)
            return pytree_add(center, e), num_updates + 1, reply
        new_center, new_n = self.server_commit(center, num_updates, payload, num_workers)
        return new_center, new_n, (new_center, new_n)

    def _set_mirror(self, wid, mirror, num_workers):
        """Store a worker's mirror, LRU-evicting stale incarnations beyond
        2×num_workers (each mirror is a full model copy; worker ids are
        per-incarnation uuids, so churn would otherwise grow this without
        bound). An evicted live worker just re-bootstraps next window.
        Replies are NOT evicted here — they carry the exactly-once
        guarantee past a mirror eviction (see __init__) and age out of
        their own LRU in _set_reply."""
        self._mirrors[wid] = mirror
        self._mirrors.move_to_end(wid)
        bound = max(2 * int(num_workers), 4)
        while len(self._mirrors) > bound:
            self._mirrors.popitem(last=False)

    def _set_reply(self, wid, reply, num_workers):
        """Record the fused reply for dedupe replay, LRU-bounded on its own
        clock at TWICE the mirror bound: a reply outlives its mirror by a
        full extra churn cycle, every dedupe replay refreshes its recency
        (an actively-retrying worker keeps its answer alive indefinitely),
        and a worker's next successful exchange supersedes it. The
        asymmetric-apply window therefore needs a lost reply AND
        4×num_workers other exchanges before the retry AND no replay
        refresh in between — or a PS restart — and is accepted as elastic
        noise (self-healing at the next bootstrap)."""
        self._last_reply[wid] = reply
        self._last_reply.move_to_end(wid)
        bound = max(4 * int(num_workers), 8)
        while len(self._last_reply) > bound:
            self._last_reply.popitem(last=False)

    def server_duplicate_reply(self, center, num_updates, payload):
        # The original reply was lost in transit after the commit applied;
        # replay the recorded answer (the mirror has already advanced, so
        # recomputing the force would double-count the diff).
        wid = payload.get("worker_id")
        if wid in self._last_reply and ("local" in payload or "elastic_diff" in payload):
            self._last_reply.move_to_end(wid)  # a retry storm keeps it pinned
            return self._last_reply[wid]
        if "local" in payload:
            return self._elastic(pytree_to_host(payload["local"]), center), num_updates
        if "elastic_diff" in payload:
            # No recorded reply (evicted, or PS restarted between the
            # original and the retry): never hand back the raw center — the
            # worker would subtract it as if it were the force. Flag a
            # re-bootstrap instead.
            zero = pytree_scale(payload["elastic_diff"], 0.0)  # stays bf16: unread
            return zero, _REBOOTSTRAP | num_updates
        return center, num_updates

    def worker_window(self, params, carry, client):
        fused = getattr(client, "commit_pull", None)
        if fused is not None and getattr(client, "wire_is_local", False):
            # In-process transport: bytes are free and replies cannot be
            # lost, so the delta-mirror machinery (bf16 casts + mirror
            # advance on BOTH sides, dedupe replay state) is pure host CPU
            # with nothing to buy — measured 1.52x-vs-sync steady state
            # against ADAG's 1.1-1.27x on loopback (a CPU timing).
            # Ship the full-precision local tree with no worker_id; the PS
            # computes and applies the force and skips all per-worker
            # bookkeeping (`if wid is not None` in server_commit_pull).
            local = pytree_to_host(params)
            e, num_updates = fused(
                {"local": local, "last_update": carry.last_update}
            )
            new_params = pytree_sub(params, _wire_f32(e))
            return new_params, WorkerCarry(
                window_start=new_params, last_update=num_updates
            )
        if fused is not None:
            wid = carry.worker_id or uuid.uuid4().hex
            local = pytree_to_host(params)
            if carry.mirror is None:
                # Bootstrap window: full-precision local; both sides then
                # hold the identical mirror ``local - e``.
                e, num_updates = fused(
                    {"local": local, "worker_id": wid,
                     "last_update": carry.last_update}
                )
                e = _wire_f32(e)
                mirror = self._round_mirror(pytree_sub(local, e))
            else:
                diff_wire = _wire_bf16(
                    pytree_sub(local, _wire_f32(carry.mirror))
                )
                e_wire, num_updates = fused(
                    {"elastic_diff": diff_wire, "worker_id": wid,
                     "last_update": carry.last_update}
                )
                if num_updates & _REBOOTSTRAP:
                    # PS lost the mirror; nothing was applied. Skip this
                    # window's exchange and re-bootstrap on the next one.
                    return params, WorkerCarry(
                        window_start=params,
                        last_update=num_updates & ~_REBOOTSTRAP,
                        worker_id=wid, mirror=None,
                    )
                e = _wire_f32(e_wire)
                # Advance the shared mirror from the wire bytes — the same
                # arithmetic, in the same order, and the same storage
                # rounding as the PS.
                mirror = self._round_mirror(
                    pytree_sub(
                        pytree_add(_wire_f32(carry.mirror), _wire_f32(diff_wire)),
                        e,
                    )
                )
            new_params = pytree_sub(params, e)
            return new_params, WorkerCarry(
                window_start=new_params, last_update=num_updates,
                worker_id=wid, mirror=mirror,
            )
        center, num_updates = client.pull()
        elastic = self._elastic(params, center)
        new_params = pytree_sub(params, elastic)
        client.commit({"delta": elastic, "last_update": num_updates})
        return new_params, WorkerCarry(window_start=new_params, last_update=num_updates)


class EAMSGDProtocol(AEASGDProtocol):
    """Elastic Averaging with Momentum SGD (reference ``EAMSGD`` trainer):
    AEASGD elastic exchange + Nesterov momentum on the local update."""

    name = "eamsgd"

    def __init__(
        self,
        communication_window: int = 32,
        rho: float = 5.0,
        learning_rate: float = 0.1,
        momentum: float = 0.9,
    ):
        super().__init__(communication_window, rho, learning_rate)
        self.momentum = float(momentum)

    def local_optimizer(self, base):
        return optax.chain(base, optax.trace(decay=self.momentum, nesterov=True))


class DynSGDProtocol(_DeltaWindowMixin, AsyncProtocol):
    """Staleness-aware dynamic SGD (reference ``DynSGD`` trainer +
    ``DynSGDParameterServer``): each committed delta is damped by the
    committer's staleness. The PS update counter is load-bearing state —
    it is owned exclusively by the PS loop, making the
    read-modify-write race-free by construction (vs the reference's
    GIL-protected handler threads)."""

    name = "dynsgd"

    def server_commit(self, center, num_updates, payload, num_workers):
        staleness = max(0, num_updates - int(payload["last_update"]))
        damped = pytree_scale(payload["delta"], 1.0 / (staleness + 1))
        return pytree_add(center, damped), num_updates + 1

    def commit_stats(self, center, num_updates, payload, num_workers):
        # The SAME damping expression server_commit applies — goodput
        # accounting must never disagree with the update rule.
        out = super().commit_stats(center, num_updates, payload, num_workers)
        staleness = out.get("staleness", 0)
        out["damping"] = 1.0 / (staleness + 1)
        return out
