"""True 1F1B pipeline schedule with a hand-rolled backward (one SPMD scan).

The scanned GPipe engine (:mod:`distkeras_tpu.parallel.pipeline`) gets its
backward from the scan's autodiff time-reversal: every (stage, tick)
residual stays live until the reversed scan consumes it, so peak
activation residency grows with the microbatch count ``M`` (measured in
``BENCH_MODE=memory benchmarks/pipeline_bench.py``). The classic fix —
the PipeDream-flush / Megatron "1F1B" schedule — cannot be expressed
through scan autodiff because it *interleaves* forward and backward work;
this module therefore writes the backward by hand.

**Schedule.** Non-interleaved 1F1B over ``P = mesh['pp']`` devices.
Device ``d`` runs the forward of microbatch ``m`` at tick ``2m + d`` and
its backward at tick ``2m + 2P - 1 - d``; the two assignments can never
collide (their tick parities differ), each device strictly alternates
F/B in steady state, neighbouring devices are phase-shifted by one tick
(activations hop ``d -> d+1``, cotangents hop ``d -> d-1``, one
``ppermute`` each per tick), and the whole step is ONE ``lax.scan`` of
``2M + 2P - 2`` ticks.

**Memory.** A device keeps only the *stage inputs* of microbatches whose
backward has not run yet — at most ``ceil((2P - 1 - 2d) / 2) <= P`` of
them, held in a ring buffer — and recomputes the stage forward inside
``jax.vjp`` at the backward tick (Megatron-style activation
recomputation). The *saved stage activations* are therefore O(P)
microbatch states per device independent of M, vs the scanned engine's
O(M·V). Total carry residency still has an M-sized term — the
``[M, B, ...]`` float32 input-cotangent buffer (``cot_out``) — so the
analytic floor is ``(min(P, M) + M)`` microbatch states, linear in M
with a much smaller constant than the scanned schedule (measured:
9-13 MB across M=8..32 vs 171-439 MB for gpipe-plain on the bench
model — the M term is ONE tensor, not one per stage tick). The
replicated ``[M, B, ...]`` microbatch inputs are additional M-linear
residency, but they live in the XLA argument buffers (reported as
``args_mb`` in the bench), not the temp/carry floor pinned above.
Compute matches the scanned engine with ``remat=True`` (one extra
forward per stage application).

**Loss placement.** 1F1B needs each microbatch's output cotangent the
tick after its last-stage forward, so the head + loss must live *inside*
the pipe: the last device's backward runs ``jax.vjp`` through
``last_fn(stage_params, head_params, x, labels)`` (stage -> head -> scalar
loss) with cotangent seed 1. The pipeline input's cotangent is emitted
per microbatch so the caller can backpropagate into the embedding that
produced the microbatches.

**Stochastic layers.** With ``rng``, each stage application receives a
key folded from (microbatch, stage, dp-slice) — deterministic, so the
backward tick's recompute reproduces the forward tick's dropout masks
exactly, and distinct across dp replicas so different data shards never
share masks.

**Data parallelism.** Pass ``io_spec`` (e.g. ``P(None, "dp")``) to shard
the microbatch batch axis: each dp slice runs its own 1F1B pipe; losses,
auxes, and parameter gradients are ``pmean``-ed over the dp axes (the
mean-loss convention), input cotangents stay dp-sharded like the inputs.

The result is a *value-and-grad* primitive, not a differentiable forward:
``pipeline_1f1b_value_and_grad`` returns the summed loss, the stacked
per-stage parameter gradients, the head gradients, and the per-microbatch
input cotangents. No reference counterpart exists (SURVEY §2: pipeline
parallelism absent from the reference entirely).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from distkeras_tpu.parallel.pipeline import stack_stage_params  # noqa: F401

__all__ = ["pipeline_1f1b_value_and_grad", "ticks_1f1b"]


def ticks_1f1b(num_microbatches: int, num_devices: int) -> int:
    """Scan length: the last backward is B_0(M-1) at ``2(M-1) + 2P - 1``."""
    return 2 * num_microbatches + 2 * num_devices - 2


def _1f1b_local(
    stage_fn, last_fn, stacked_params, head_params, microbatches, labels,
    rng, axis_name: str, varying_axes=(), with_aux: bool = False,
    stage_aux_seed: float | None = None,
):
    """Per-device body (inside shard_map over ``axis_name`` + any dp axes).

    ``stage_aux_seed`` switches on the MoE mode: ``stage_fn`` returns
    ``(y, aux_raw)`` and ``last_fn`` returns ``(loss, aux_raw)`` (or
    ``(loss, aux_raw, metrics_aux)`` under ``with_aux``), where
    ``aux_raw`` is a differentiated auxiliary loss (the MoE load-balance
    term). Each backward tick's vjp seeds the aux output with the scalar
    ``stage_aux_seed`` (the caller folds its weight and 1/M there), so the
    optimized total is ``sum(loss) + seed * sum(aux_raw)`` while the
    returned loss value stays the pure task loss; the raw aux sum is
    accumulated separately for metrics. Expert-parallel stages (a psum
    over an ``ep`` mesh axis inside ``stage_fn``) are safe here: the
    branch predicates vary over ``axis_name`` only, every ep peer of a pp
    row takes the same branch, and activations/loss stay ep-INVARIANT
    (the forward psum removes the ep axis from the vma), so autodiff
    inserts only ep-psums inside branches — never the pp/dp psums that
    deadlock (the reason everything else is pcast varying below).
    """
    d = lax.axis_index(axis_name)
    num_devices = lax.axis_size(axis_name)
    M, B = microbatches.shape[0], microbatches.shape[1]
    feat = microbatches.shape[2:]
    dtype = microbatches.dtype
    Pd = num_devices
    all_axes = (axis_name, *varying_axes)
    moe = stage_aux_seed is not None

    my_params = jax.tree.map(lambda x: x[0], stacked_params)  # [1,...] shard
    fwd_perm = [(i, (i + 1) % Pd) for i in range(Pd)]
    bwd_perm = [(i, (i - 1) % Pd) for i in range(Pd)]

    def varying(x):
        have = jax.typeof(x).vma
        need = tuple(a for a in all_axes if a not in have)
        return lax.pcast(x, need, to="varying") if need else x

    # CRITICAL: the head params must be varying before any vjp touches
    # them. Taking a cotangent w.r.t. an axis-INVARIANT input makes JAX
    # close the transpose with a psum over that axis — and here the vjp
    # runs inside a cond branch only the last pp row takes, so that psum
    # would be a collective inside a divergent branch: a lock-step
    # deadlock (observed as an XLA rendezvous timeout). Varying inputs
    # need no such psum; the reductions happen once, after the scan, on
    # the accumulated values.
    head_params = jax.tree.map(varying, head_params)
    my_params = jax.tree.map(varying, my_params)

    zero_state = jnp.zeros((B, *feat), dtype)
    carry0 = dict(
        act_in=varying(zero_state),            # activation arriving for F
        cot_in=varying(zero_state.astype(jnp.float32)),  # arriving cotangent
        ring=varying(jnp.zeros((Pd, B, *feat), dtype)),  # in-flight inputs
        grads=jax.tree.map(lambda x: varying(jnp.zeros_like(x)), my_params),
        head_grads=jax.tree.map(
            lambda x: varying(jnp.zeros_like(x, dtype=jnp.float32)),
            head_params,
        ),
        loss=varying(jnp.float32(0.0)),
        aux=varying(jnp.float32(0.0)),
        moe_aux=varying(jnp.float32(0.0)),
        cot_out=varying(jnp.zeros((M, B, *feat), jnp.float32)),
    )

    last = Pd - 1

    def key_for(m):
        # Deterministic per (microbatch, stage, dp-slice): the backward
        # recompute reproduces the forward's dropout masks exactly, and dp
        # replicas (different data shards) get independent masks.
        if rng is None:
            return None
        key = jax.random.fold_in(jax.random.fold_in(rng, m), d)
        for ax in varying_axes:
            key = jax.random.fold_in(key, lax.axis_index(ax))
        return key

    def apply_stage(p, x, m):
        # moe mode: returns (y, aux_raw); otherwise just y.
        if rng is None:
            return stage_fn(p, x)
        return stage_fn(p, x, key_for(m))

    def apply_last(p, hp, x, yl, m):
        # Normalized to ((loss, stage_aux), metrics_aux): the first pair is
        # differentiated (aux seeded with stage_aux_seed in moe mode), the
        # metrics channel rides has_aux.
        if rng is None:
            out = last_fn(p, hp, x, yl)
        else:
            out = last_fn(p, hp, x, yl, key_for(m))
        if moe:
            if with_aux:
                loss, saux, maux = out
            else:
                (loss, saux), maux = out, jnp.float32(0.0)
            return (loss, saux), maux
        if with_aux:
            loss, maux = out
        else:
            loss, maux = out, jnp.float32(0.0)
        return (loss, jnp.float32(0.0)), maux

    def make_tick(enable_f: bool, enable_b: bool):
        """One scan body, specialized to the phase (static at trace time):

        - fill  (ticks 0..P-1):       no backward exists anywhere (the
          earliest B tick is 2P-1-(P-1) = P), so the cotangent ppermute is
          statically dead — elide it, and compile no b_branch at all;
        - steady (ticks P..2M+P-3):   both hops, the full 3-way switch;
        - drain (ticks 2M+P-2..T-1):  no forward exists anywhere (the
          latest F tick is 2(M-1)+P-1 = 2M+P-3) and the activation sent at
          2M+P-3 is never consumed, so the activation ppermute is
          statically dead — elide it, and compile no f_branch.

        This removes P full-size hops per direction per step (all of the
        fill phase's cotangent traffic and the drain phase's activation
        traffic) and shrinks the fill/drain scan
        bodies to single-role conds.
        """
        assert enable_f or enable_b

        return partial(_tick, enable_f, enable_b)

    def _tick(enable_f, enable_b, carry, t):
        # Role this tick (mutually exclusive by parity — see module doc).
        mf2, mb2 = t - d, t - (2 * Pd - 1 - d)
        is_f = (mf2 >= 0) & (mf2 % 2 == 0) & (mf2 // 2 < M)
        is_b = (mb2 >= 0) & (mb2 % 2 == 0) & (mb2 // 2 < M)
        m_f = jnp.clip(mf2 // 2, 0, M - 1)
        m_b = jnp.clip(mb2 // 2, 0, M - 1)

        def f_branch(c):
            x_feed = lax.dynamic_index_in_dim(microbatches, m_f, 0, False)
            x = jnp.where(d == 0, x_feed, c["act_in"])
            ring = lax.dynamic_update_index_in_dim(c["ring"], x, m_f % Pd, 0)
            # The last device's F output is never consumed (its B tick
            # recomputes through the vjp) — genuinely skip the stage math
            # there via cond (a where would still evaluate apply_stage,
            # charging the last stage one discarded forward per
            # microbatch). Safe: stage_fn is collective-free over pp/dp
            # under the 1F1B constraints, so branch divergence across pp
            # rows cannot deadlock.
            if moe:
                def run_stage(xx):
                    yy, _aux = apply_stage(my_params, xx, m_f)
                    return yy  # aux is accounted once, at the B-tick recompute
            else:
                def run_stage(xx):
                    return apply_stage(my_params, xx, m_f)
            y = lax.cond(
                d == last,
                lambda xx: varying(jnp.zeros_like(xx)),
                run_stage,
                x,
            )
            return (
                dict(c, ring=ring), y,
                varying(jnp.zeros((B, *feat), jnp.float32)),
            )

        def b_branch(c):
            x = lax.dynamic_index_in_dim(c["ring"], m_b % Pd, 0, False)

            def last_loss(p, hp, xx):
                # ((loss, stage_aux), metrics_aux) — see apply_last.
                yl = lax.dynamic_index_in_dim(labels, m_b, 0, False)
                return apply_last(p, hp, xx, yl, m_b)

            def mid_apply(p, xx):
                return apply_stage(p, xx, m_b)

            def do_last(_):
                (loss_m, saux_m), vjp, aux_m = jax.vjp(
                    last_loss, my_params, head_params, x, has_aux=True
                )
                if moe:
                    # Seed the aux-loss output with the caller's weight so
                    # its gradient (router load balance) flows alongside the
                    # task loss through the SAME recompute.
                    gp, ghp, gx = vjp((
                        jnp.ones_like(loss_m),
                        jnp.full_like(saux_m, stage_aux_seed),
                    ))
                else:
                    gp, ghp, gx = vjp((
                        jnp.ones_like(loss_m), jnp.zeros_like(saux_m)
                    ))
                # f32 accumulators regardless of head param dtype.
                ghp = jax.tree.map(lambda g: g.astype(jnp.float32), ghp)
                return (
                    loss_m.astype(jnp.float32),
                    # with_aux=False feeds a fresh (invariant) zero here;
                    # match the other branch's varying type.
                    varying(aux_m.astype(jnp.float32)),
                    varying(saux_m.astype(jnp.float32)),
                    gp, ghp, gx.astype(jnp.float32),
                )

            def do_mid(_):
                if moe:
                    (_, saux_m), vjp = jax.vjp(mid_apply, my_params, x)
                    gp, gx = vjp((
                        c["cot_in"].astype(dtype),
                        jnp.full_like(saux_m, stage_aux_seed),
                    ))
                else:
                    _, vjp = jax.vjp(mid_apply, my_params, x)
                    gp, gx = vjp(c["cot_in"].astype(dtype))
                    saux_m = jnp.float32(0.0)
                # Fresh zeros are axis-invariant; the cond's other branch
                # returns varying values — match the types explicitly.
                return (
                    varying(jnp.float32(0.0)), varying(jnp.float32(0.0)),
                    varying(saux_m.astype(jnp.float32)),
                    gp,
                    jax.tree.map(
                        lambda z: varying(jnp.zeros_like(z)),
                        c["head_grads"],
                    ),
                    gx.astype(jnp.float32),
                )

            loss_m, aux_m, saux_m, gp, ghp, gx = lax.cond(
                d == last, do_last, do_mid, None
            )
            grads = jax.tree.map(jnp.add, c["grads"], gp)
            head_grads = jax.tree.map(jnp.add, c["head_grads"], ghp)
            # Device 0's input cotangent feeds the embedding backward.
            cot_out = jnp.where(
                d == 0,
                lax.dynamic_update_index_in_dim(c["cot_out"], gx, m_b, 0),
                c["cot_out"],
            )
            return (
                dict(c, grads=grads, head_grads=head_grads,
                     loss=c["loss"] + loss_m, aux=c["aux"] + aux_m,
                     moe_aux=c["moe_aux"] + saux_m,
                     cot_out=cot_out),
                varying(jnp.zeros((B, *feat), dtype)),
                gx,
            )

        def idle(c):
            return (
                c,
                varying(jnp.zeros((B, *feat), dtype)),
                varying(jnp.zeros((B, *feat), jnp.float32)),
            )

        if enable_f and enable_b:
            role = jnp.where(is_f, 1, jnp.where(is_b, 2, 0))
            carry, y_send, cot_send = lax.switch(
                role, [idle, f_branch, b_branch], carry
            )
        elif enable_f:
            carry, y_send, cot_send = lax.cond(is_f, f_branch, idle, carry)
        else:
            carry, y_send, cot_send = lax.cond(is_b, b_branch, idle, carry)
        # Collectives run unconditionally (outside the switch) on every
        # tick of their phase — lock-step across pp rows by construction.
        updates = {}
        if enable_f:
            updates["act_in"] = lax.ppermute(y_send, axis_name, fwd_perm)
        if enable_b:
            updates["cot_in"] = lax.ppermute(
                cot_send.astype(jnp.float32), axis_name, bwd_perm
            )
        return dict(carry, **updates), None

    # Three statically-specialized phases (see make_tick): boundaries from
    # the tick algebra — B ticks live in [P, 2M+2P-3], F in [0, 2M+P-3].
    T = ticks_1f1b(M, Pd)
    fill_end = min(Pd, T)
    drain_start = max(2 * M + Pd - 2, fill_end)
    carry, _ = lax.scan(make_tick(True, False), carry0, jnp.arange(fill_end))
    carry, _ = lax.scan(
        make_tick(True, True), carry, jnp.arange(fill_end, drain_start)
    )
    carry, _ = lax.scan(
        make_tick(False, True), carry, jnp.arange(drain_start, T)
    )
    # Disjoint sums over pp (loss/aux/head_grads live on the last pp row,
    # cot_out on row 0); means over any dp axes — the mean-loss convention
    # (each dp slice computed its shard's mean loss).
    loss = lax.psum(carry["loss"], axis_name)
    aux = lax.psum(carry["aux"], axis_name)
    # Per-stage aux losses live disjointly on their own pp rows (each stage
    # accumulated its own layers' aux at its B ticks) — a psum collects.
    moe_aux = lax.psum(carry["moe_aux"], axis_name)
    head_grads = jax.tree.map(
        lambda g: lax.psum(g, axis_name), carry["head_grads"]
    )
    stage_grads = carry["grads"]
    for ax in varying_axes:
        loss = lax.pmean(loss, ax)
        aux = lax.pmean(aux, ax)
        moe_aux = lax.pmean(moe_aux, ax)
        head_grads = jax.tree.map(lambda g: lax.pmean(g, ax), head_grads)
        stage_grads = jax.tree.map(lambda g: lax.pmean(g, ax), stage_grads)
    cot_out = lax.psum(carry["cot_out"], axis_name)
    # The cotangents must match the mean-loss convention of the pmean-ed
    # grads above: each dp slice computed the cotangent of ITS shard-mean
    # loss, and the global loss is the pmean — scale by 1/dp so the
    # caller's embedding vjp lands gradients on the same scale as the
    # stage/head grads (they stay dp-sharded like the inputs).
    for ax in varying_axes:
        cot_out = cot_out / lax.axis_size(ax)
    stage_grads = jax.tree.map(lambda g: g[None], stage_grads)
    out = (loss,)
    if with_aux:
        out += (aux,)
    if moe:
        out += (moe_aux,)
    return out + (stage_grads, head_grads, cot_out)


def pipeline_1f1b_value_and_grad(
    stage_fn,
    last_fn,
    stacked_params,
    head_params,
    microbatches,
    labels,
    mesh: Mesh,
    axis_name: str = "pp",
    rng=None,
    with_aux: bool = False,
    io_spec: P | None = None,
    param_specs=None,
    stage_aux_seed: float | None = None,
):
    """Run one 1F1B train-step evaluation over ``mesh[axis_name]``.

    - ``stage_fn(stage_params, x) -> y`` (``stage_fn(p, x, key)`` when
      ``rng`` is given) with ``y.shape == x.shape`` — applied by devices
      ``0 .. P-2`` and recomputed inside the last device's vjp;
    - ``last_fn(stage_params, head_params, x, labels_mb) -> scalar loss``
      (``(loss, aux_scalar)`` when ``with_aux``; extra ``key`` arg when
      ``rng`` is given) — the last stage *including head and loss* for
      one microbatch;
    - ``stacked_params``: PyTree with leading stage axis ``[P, ...]``
      (:func:`stack_stage_params`), sharded over ``axis_name``;
    - ``head_params``: replicated head/loss params;
    - ``microbatches``: ``[M, B, ...]``; ``labels``: ``[M, ...]``. By
      default replicated; pass ``io_spec`` (e.g. ``P(None, "dp")``) to
      shard the batch axis over dp — each dp slice runs its own pipe and
      losses/grads are pmean-ed (mean-loss convention).

    With ``stage_aux_seed`` (MoE mode): ``stage_fn`` returns
    ``(y, aux_raw)`` and ``last_fn`` returns ``(loss, aux_raw)`` (plus the
    metrics channel under ``with_aux``); every backward vjp seeds the aux
    output with ``stage_aux_seed`` so the optimized total is
    ``sum(loss) + seed*sum(aux_raw)``, and the raw aux sum is returned for
    metrics. Pass ``param_specs`` to shard expert-weight leaves
    ``P(axis_name, "ep")`` for expert parallelism inside the pipe (the
    stage fn runs the MoE block in manual-collective mode and psums over
    ``ep``; see the module docstring on why that composes safely with the
    divergent tick branches).

    Returns ``(loss_sum[, aux_sum][, moe_aux_sum], stage_grads,
    head_grads, input_cotangents)``: the summed microbatch losses (and
    aux channels), gradients stacked ``[P, ...]`` over the stage axis
    (expert leaves keep their ``param_specs`` sharding), head gradients,
    and ``[M, B, ...]`` input cotangents (float32, sharded like the
    inputs) for the caller's embedding backward. Divide by ``M`` for
    means. Saved stage activations are O(P) microbatch states (ring
    buffer); total residency adds one M-sized input-cotangent buffer —
    ``(min(P, M) + M)`` states, see the module docstring.
    """
    from jax import shard_map

    if io_spec is None:
        io_spec = P()
    varying_axes = tuple(
        ax
        for entry in io_spec
        if entry is not None
        for ax in ((entry,) if isinstance(entry, str) else tuple(entry))
        if ax != axis_name
    )
    # param_specs carries expert-parallel shardings (e.g. P("pp", "ep") on
    # MoE expert-weight leaves): each pp row's ep group holds a slice of
    # that stage's experts, and the returned gradients come back with the
    # SAME specs (expert grads stay ep-sharded — they are exact local
    # grads, no cross-ep reduction exists for disjoint expert slices).
    spec_p = (
        param_specs
        if param_specs is not None
        else jax.tree.map(lambda _: P(axis_name), stacked_params)
    )
    n_out = 4 + int(with_aux) + int(stage_aux_seed is not None)
    out_specs = (
        (P(),) * (n_out - 3)
        + (
            spec_p,
            jax.tree.map(lambda _: P(), head_params),
            io_spec,
        )
    )
    fn = shard_map(
        partial(
            _1f1b_local, stage_fn, last_fn, axis_name=axis_name,
            varying_axes=varying_axes, with_aux=with_aux,
            stage_aux_seed=stage_aux_seed,
        ),
        mesh=mesh,
        in_specs=(spec_p, P(), io_spec, io_spec, P()),
        out_specs=out_specs,
    )
    lead = jax.tree.leaves(stacked_params)[0].shape[0]
    if lead != mesh.shape[axis_name]:
        raise ValueError(
            f"stacked params have {lead} stages but mesh {axis_name}="
            f"{mesh.shape[axis_name]} (1F1B is non-interleaved: V=1)"
        )
    return fn(stacked_params, head_params, microbatches, labels, rng)
