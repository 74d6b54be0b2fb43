"""Pipeline parallelism over a ``pp`` mesh axis (GPipe + Megatron-style
interleaved virtual stages; the backward is the scan's autodiff
time-reversal — GPipe-ordered, not 1F1B). The activation-memory price of
that choice is measured, not guessed: ``BENCH_MODE=memory
benchmarks/pipeline_bench.py`` reports XLA's compiled peak temp per
schedule (plain vs remat, V=1 vs 2) next to the TRUE 1F1B engine
(:mod:`distkeras_tpu.parallel.pipeline_1f1b` — hand-rolled backward,
near-flat residency in M: O(P) saved stage activations plus one M-sized
cotangent buffer); the (model, M, V, P)-fits-16GB table lives in
docs/parallel.md.

Absent from the reference (SURVEY §2 parallelism table) but a first-class
axis here. The design is SPMD, not a scheduler: every device runs the same
program under ``shard_map``; stage identity comes from ``lax.axis_index``.
Per tick, each device applies one of its stages to its current activation
and rotates activations one hop forward with ``lax.ppermute`` (ICI neighbor
traffic only).

**Schedule.** With ``V = virtual_stages`` chunks per device (Megatron-style
interleaving), the ``L = V·P`` logical stages are laid out round-robin:
device ``d`` owns logical stages ``{d, P+d, …, (V-1)·P+d}``. Microbatches
inject in groups of ``P`` at ticks ``inj(m) = (m//P)·V·P + m%P``; an
activation processed on device ``P-1`` for chunk ``v`` re-enters device 0
for chunk ``v+1`` on the very next tick, so nothing ever queues and the
lock-step rotation stays exact. Device ``d`` is busy every tick of
``[d, d+M·V)`` processing chunk ``v(τ) = (τ//P) mod V`` of microbatch
``m(τ) = (τ//(V·P))·P + τ%P`` where ``τ = t - d``. When ``P | M`` the total
is ``M·V + P - 1`` ticks and the fill/drain bubble is ``(P-1)/(M·V+P-1)`` —
**V× smaller per unit work** than the V=1 GPipe schedule's ``(P-1)/(M+P-1)``
(same-depth model, stages V× shallower). A ragged last group (``P ∤ M``)
still computes correctly but stalls up to one extra V·P round
(T = ((M-1)//P)·V·P + (M-1)%P + V·P); size ``M`` as a multiple of ``P`` to
get the advertised bubble. V=1 reduces to plain GPipe.

Constraints (by construction of the rotation): every stage maps activations
of one shape to the same shape — the transformer-block case. Embedding/head
layers stay outside the pipelined trunk.

The whole schedule is a ``lax.scan``, so it differentiates: gradients flow
back through the ppermutes (reverse hops) and the per-stage applications,
giving pipeline-parallel *training*, not just inference. (The backward is
the scan's time-reversal — activation memory is the remat lever on
``stage_fn``, not the schedule; see PipelineTrainer's ``remat``, or
``schedule="1f1b"`` for the hand-rolled schedule whose residency is
near-flat in M — and which composes with MoE/ep since round 5.)
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "pipeline_apply",
    "stack_stage_params",
    "stage_param_specs",
    "pipeline_shardings",
    "schedule_ticks",
]


def stage_param_specs(stacked, ep_size: int = 1):
    """Per-leaf PartitionSpecs for stacked stage params: the stage axis
    shards over ``pp`` everywhere; MoE expert-weight leaves
    (``moe_mlp/w_in|w_out`` — leading stage dim, then the expert dim)
    additionally shard their expert dim over ``ep`` when ``ep_size > 1``.
    The router stays replicated over ep (every member routes the full
    token set). The ONE definition of the rule — the trainer, the memory
    bench, and the dryrun must all agree on which leaves are experts."""

    def spec(path, _leaf):
        if ep_size > 1:
            keys = [getattr(k, "key", None) for k in path]
            if "moe_mlp" in keys and keys[-1] in ("w_in", "w_out"):
                return P("pp", "ep")
        return P("pp")

    return jax.tree_util.tree_map_with_path(spec, stacked)


def schedule_ticks(num_microbatches: int, num_devices: int,
                   virtual_stages: int = 1) -> int:
    """Total scan ticks of the interleaved schedule: microbatch ``M-1``
    injects at ``((M-1)//P)·V·P + (M-1)%P`` and takes ``V·P`` ticks to
    drain. The ONE definition — the scan body, the schedule bench, and the
    memory bench all derive their tick counts from it."""
    M, P, V = num_microbatches, num_devices, virtual_stages
    return ((M - 1) // P) * V * P + (M - 1) % P + V * P


def stack_stage_params(stage_params_list, virtual_stages: int = 1):
    """Stack per-stage parameter PyTrees on a leading 'stage' axis
    ([L, ...] leaves) — shard that axis over ``pp``.

    ``stage_params_list`` is in **logical order** (stage 0 first). With
    ``virtual_stages=V > 1`` the stack is permuted to the round-robin device
    layout the interleaved schedule expects: position ``d·V + v`` holds
    logical stage ``v·P + d``, so the pp-sharding's contiguous split hands
    device ``d`` exactly its V chunks, indexable by ``v``.
    """
    L = len(stage_params_list)
    if L % virtual_stages:
        raise ValueError(
            f"{L} stages not divisible by virtual_stages={virtual_stages}"
        )
    if virtual_stages > 1:
        num_devices = L // virtual_stages
        order = [
            v * num_devices + d
            for d in range(num_devices)
            for v in range(virtual_stages)
        ]
        stage_params_list = [stage_params_list[i] for i in order]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *stage_params_list)


def _io_spec(mesh: Mesh) -> P:
    """Microbatch spec ``[M, B, ...]``: shard the batch axis over ``dp``
    when the mesh has one (each dp slice runs its own pipeline replica over
    the pp axis) instead of replicating the whole feed to every device."""
    if "dp" in mesh.axis_names and mesh.shape["dp"] > 1:
        return P(None, "dp")
    return P()


def pipeline_shardings(mesh: Mesh):
    """(stacked_params_sharding, io_sharding) for :func:`pipeline_apply`."""
    params = NamedSharding(mesh, P("pp"))
    io = NamedSharding(mesh, _io_spec(mesh))
    return params, io


def _pipeline_local(
    stage_fn, stacked_params, microbatches, rng, axis_name: str,
    virtual_stages: int, varying_axes=(), with_aux: bool = False,
):
    """Per-device body (inside shard_map).

    ``stacked_params``: this device's chunk params ([V, ...] leaves — the
    'pp'-sharded round-robin stack). ``microbatches``: [M, B, D]. Returns
    [M, B, D]: final-stage outputs (valid on every device: one psum after
    the scan broadcasts them, keeping collectives off the scan's critical
    path).
    """
    d = lax.axis_index(axis_name)
    num_devices = lax.axis_size(axis_name)
    V = virtual_stages
    M, B = microbatches.shape[0], microbatches.shape[1]
    feat_shape = microbatches.shape[2:]
    perm = [(i, (i + 1) % num_devices) for i in range(num_devices)]

    def v_of(tau):
        return (tau // num_devices) % V

    def m_of(tau):
        return (tau // (V * num_devices)) * num_devices + tau % num_devices

    # The carry must be device-varying over the pp axis from the start
    # (ppermute outputs are varying; scan carries must type-match) — and
    # over any axis the microbatches are sharded on (dp io sharding makes
    # the ingested state dp-varying too).
    zeros = jnp.zeros((B, *feat_shape), microbatches.dtype)
    state = lax.pcast(zeros, (axis_name, *varying_axes), to="varying")
    out_buf = lax.pcast(
        jnp.zeros((M, B, *feat_shape), microbatches.dtype),
        (axis_name, *varying_axes),
        to="varying",
    )
    aux_acc = lax.pcast(
        jnp.zeros((), jnp.float32), (axis_name, *varying_axes), to="varying"
    )

    def tick(carry, t):
        state, out_buf, aux_acc = carry
        tau = t - d
        v = v_of(tau)
        m = m_of(tau)
        m_clip = jnp.clip(m, 0, M - 1)
        # device 0 ingests microbatch m when it starts chunk 0
        x_in = lax.dynamic_index_in_dim(
            microbatches, m_clip, axis=0, keepdims=False
        )
        ingest = (d == 0) & (v == 0) & (tau >= 0) & (m < M)
        state = jnp.where(ingest, x_in, state)
        my_params = jax.tree.map(
            lambda x: lax.dynamic_index_in_dim(x, v, axis=0, keepdims=False),
            stacked_params,
        )
        if rng is None:
            y = stage_fn(my_params, state)
        else:
            # unique stream per (tick, device, dp-slice): stochastic layers
            # (dropout) get fresh masks for every stage application of
            # every microbatch — including across dp replicas, whose data
            # shards differ and must not share masks
            key = jax.random.fold_in(jax.random.fold_in(rng, t), d)
            for _ax in varying_axes:
                key = jax.random.fold_in(key, lax.axis_index(_ax))
            y = stage_fn(my_params, state, key)
        if with_aux:
            y, aux = y
            # only real (stage, microbatch) applications contribute — the
            # fill/drain garbage ticks run on zero states and are masked out
            valid = (tau >= 0) & (m < M)
            aux_acc = aux_acc + jnp.where(valid, aux.astype(jnp.float32), 0.0)
        # the last device at its last chunk owns microbatch m's final output
        emit = (d == num_devices - 1) & (v == V - 1) & (tau >= 0) & (m < M)
        emitted = jnp.where(emit, y, jnp.zeros_like(y))
        out_buf = out_buf.at[m_clip].add(emitted)
        state = lax.ppermute(y, axis_name, perm)
        return (state, out_buf, aux_acc), None

    # Static tick count: last microbatch M-1 emits at inj(M-1) + V·P - 1
    # (axis_size of a mesh axis is a static int, so T is trace-time known).
    T = schedule_ticks(M, num_devices, V)
    (_, out_buf, aux_acc), _ = lax.scan(
        tick, (state, out_buf, aux_acc), jnp.arange(T)
    )
    out = lax.psum(out_buf, axis_name)
    if not with_aux:
        return out
    # every (logical stage, microbatch) pair ran on exactly one device: the
    # psum over pp is a disjoint sum, and dp replicas (different batch
    # shards) average.
    aux = lax.psum(aux_acc, axis_name)
    for ax in varying_axes:
        aux = lax.pmean(aux, ax)
    return out, aux


def pipeline_apply(
    stage_fn,
    stacked_params,
    microbatches,
    mesh: Mesh,
    axis_name: str = "pp",
    io_spec: P | None = None,
    virtual_stages: int = 1,
    rng=None,
    with_aux: bool = False,
    param_specs=None,
):
    """Run an ``L``-stage pipeline over ``mesh[axis_name]``.

    - ``stage_fn(params, x) -> y`` with ``y.shape == x.shape``;
    - ``stacked_params``: PyTree with leading stage axis (see
      :func:`stack_stage_params` — pass it the same ``virtual_stages`` so
      the round-robin layout matches), sharded over ``axis_name``;
    - ``microbatches``: ``[M, B, ...]`` array. By default the batch axis
      shards over the mesh's ``dp`` axis when present (each dp slice runs
      its own pipeline replica); pass ``io_spec`` to override.
    - ``virtual_stages``: chunks per device (interleaved schedule); the
      fill/drain bubble shrinks ~V× at the cost of V× more (shallower)
      stage applications per tick window.
    - ``rng``: optional PRNG key. When given, ``stage_fn`` is called as
      ``stage_fn(params, x, key)`` with a key unique per (tick, device) —
      the hook for stochastic layers (dropout) inside the pipelined trunk.
    - ``with_aux``: ``stage_fn`` returns ``(y, aux_scalar)``; the scalars
      from every real stage application are summed across stages, summed
      across microbatches, and averaged over dp replicas — the MoE
      load-balance-loss plumbing. Returns ``(outputs, aux_sum)``; divide by
      M for the per-batch mean.

    Returns ``[M, B, ...]`` — the final stage's outputs (plus the aux sum
    when ``with_aux``). Differentiable end-to-end.
    """
    from jax import shard_map

    if io_spec is None:
        io_spec = _io_spec(mesh)
    varying_axes = tuple(
        ax
        for entry in io_spec
        if entry is not None
        for ax in ((entry,) if isinstance(entry, str) else tuple(entry))
        if ax != axis_name
    )
    if param_specs is None:
        # Uniform default: stage axis over pp, everything else replicated.
        # Callers sharding further axes (e.g. expert dims over ep for a
        # pipelined MoE — the stage_fn then owns the matching collectives)
        # pass a per-leaf spec tree.
        param_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)
    fn = shard_map(
        partial(
            _pipeline_local, stage_fn, axis_name=axis_name,
            virtual_stages=virtual_stages, varying_axes=varying_axes,
            with_aux=with_aux,
        ),
        mesh=mesh,
        in_specs=(param_specs, io_spec, P()),
        out_specs=(io_spec, P()) if with_aux else io_spec,
    )
    if microbatches.shape[0] < 1:
        raise ValueError("need at least one microbatch")
    expected = virtual_stages * mesh.shape[axis_name]
    lead = jax.tree.leaves(stacked_params)[0].shape[0]
    if lead != expected:
        raise ValueError(
            f"stacked params have {lead} stages but mesh {axis_name}="
            f"{mesh.shape[axis_name]} x virtual_stages={virtual_stages} "
            f"needs {expected} — pass the same virtual_stages to "
            f"stack_stage_params and pipeline_apply"
        )
    return fn(stacked_params, microbatches, rng)
