"""The main path's Pallas kernels, compiled for a TPU that is described and
not attached (``v5e:2x2``), at the widths chip_smoke.py runs them.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: a slice off the tiling, too much fast memory, a kernel
that cannot be partitioned. These compiles can, at no chip time — each
asserts the Mosaic kernel is really in the executable
(``tpu_custom_call``), i.e. nothing fell to interpret. Nothing runs: a
compile that passes says nothing about results or speed.
"""

import functools
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp
# Nothing here touches a chip, so several test processes (xdist workers)
# may hold libtpu at once instead of fighting over its lockfile.
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from distkeras_tpu.ops.pallas.flash_attention import flash_attention
from distkeras_tpu.ops.pallas.fused_xent import fused_softmax_xent
from distkeras_tpu.ops.ring_flash import ring_flash_attention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or it cannot describe a v5e
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e!r}")


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip (the next one warns and
    compiles again) — keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _assert_kernel_compiles(fn, *avals):
    text = jax.jit(fn).lower(*avals).compile().as_text()
    assert "tpu_custom_call" in text


def _qkv(shape, sharding):
    return [jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)] * 3


# gpt_small-shaped causal attention; and bert_base_mlm's, non-causal, at
# the smoke's train batch.
FLASH_CASES = [((8, 512, 12, 64), True), ((16, 512, 12, 64), False)]


@pytest.mark.parametrize("shape,causal", FLASH_CASES)
def test_flash_attention_forward_compiles_for_v5e(topo, shape, causal):
    one_chip = SingleDeviceSharding(topo.devices[0])
    _assert_kernel_compiles(
        lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                        interpret=False),
        *_qkv(shape, one_chip))


@pytest.mark.parametrize("shape,causal", FLASH_CASES)
def test_flash_attention_grad_compiles_for_v5e(topo, shape, causal):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=causal, interpret=False)
        return out.astype(jnp.float32).sum()

    _assert_kernel_compiles(jax.grad(loss, argnums=(0, 1, 2)),
                            *_qkv(shape, one_chip))


# bert_base_mlm's head at 2 x 512 tokens in bf16, gpt_small's at 4 x 512 in
# f32: both vocabularies are ragged against every tile size.
@pytest.mark.parametrize("tokens,vocab,dtype", [
    (1024, 30522, jnp.bfloat16), (2048, 50257, jnp.float32)])
def test_fused_softmax_xent_fwd_and_grad_compile_for_v5e(topo, tokens, vocab,
                                                         dtype):
    one_chip = SingleDeviceSharding(topo.devices[0])
    logits = jax.ShapeDtypeStruct((tokens, vocab), dtype, sharding=one_chip)
    labels = jax.ShapeDtypeStruct((tokens,), jnp.int32, sharding=one_chip)
    _assert_kernel_compiles(
        jax.value_and_grad(
            lambda lg, lb: fused_softmax_xent(lg, lb, interpret=False)),
        logits, labels)


def test_ring_flash_attention_grad_compiles_for_four_v5e_chips(topo):
    mesh = Mesh(np.array(topo.devices).reshape(4), ("sp",))
    seq_sharded = NamedSharding(mesh, P(None, "sp"))

    def loss(q, k, v):
        out = ring_flash_attention(q, k, v, mesh, seq_axis="sp", causal=True,
                                   interpret=False)
        return out.astype(jnp.float32).sum()

    _assert_kernel_compiles(jax.grad(loss, argnums=(0, 1, 2)),
                            *_qkv((2, 4096, 12, 64), seq_sharded))


# -- the serving engine's paged programs, at gpt2-small.gen_closed's flags ----
#
# `run serve --model gpt_small --paged --kv-block-tokens 16 --slots 64
# --kv-pool-mb 8192 --max-context 1024`: 24 pool leaves of 14,563 blocks.
# What is held here is structural (it is in the compiled program or it is
# not): the donated pool is written and read in place as stored. With a leaf
# declared [C, bt, H, D] the donation held just the same and every program
# still converted each whole leaf's layout three times (PR 26).
_SLOTS, _BLOCK_TOKENS, _CONTEXT, _POOL_BLOCKS = 64, 16, 1024, 14563


def _paged_program(topo, which, tp, vocab, layers):
    """The engine's own paged step (`serving/engine.py`), jitted the way
    `_sharded_jit` does, with the shapes it is called with: (jitted,
    arguments, the pool leaves)."""
    from distkeras_tpu import models
    from distkeras_tpu.inference.generate import _decode_module
    from distkeras_tpu.parallel.sharding import (
        infer_variable_shardings,
        kv_pytree_shardings,
        unbox,
    )
    from distkeras_tpu.serving import engine

    table = -(-_CONTEXT // _BLOCK_TOKENS)
    mesh = None
    if tp > 1:
        mesh = Mesh(np.array(topo.devices[:tp]).reshape(1, tp), ("dp", "tp"))
    module, _ = _decode_module(
        models.gpt_small(seq_len=_CONTEXT, vocab_size=vocab), slots=True,
        paged_blocks=_POOL_BLOCKS, page_tokens=_BLOCK_TOKENS,
        page_table_blocks=table, tp_mesh=mesh, num_layers=layers)
    abstract = jax.eval_shape(
        lambda r: module.init(r, jnp.zeros((_SLOTS, 1), jnp.int32),
                              train=False), jax.random.PRNGKey(0))
    params, cache = unbox(abstract["params"]), abstract["cache"]
    if mesh is None:
        rep = SingleDeviceSharding(topo.devices[0])
        psh = jax.tree.map(lambda _: rep, params)
        csh = jax.tree.map(lambda _: rep, cache)
    else:
        rep = NamedSharding(mesh, P())
        psh = infer_variable_shardings(mesh, abstract)["params"]
        csh = kv_pytree_shardings(mesh, cache)

    def placed(tree, shardings):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, shardings)

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    key = arg(jnp.uint32, 2)
    if which == "decode":
        fn = functools.partial(engine._paged_decode_fn, module, 0,
                               _POOL_BLOCKS)
        args = (arg(jnp.int32, _SLOTS), arg(jnp.float32, _SLOTS),
                arg(jnp.int32, _SLOTS), arg(jnp.int32, _SLOTS, table), key)
        out_sh = (csh, rep, rep)
    else:  # one prefill bucket: 128 tokens, the cell's longest prompt
        fn = functools.partial(engine._paged_prefill_fn, module, 0)
        args = (arg(jnp.int32, 1, 128), arg(jnp.int32), arg(jnp.int32),
                arg(jnp.int32, table), arg(jnp.float32), key)
        out_sh = (csh, rep)
    jitted = jax.jit(fn, donate_argnums=(1,),
                     **({} if mesh is None else {"out_shardings": out_sh}))
    leaves = [a for a in jax.tree.leaves(cache) if a.ndim > 1]
    return jitted, (placed(params, psh), placed(cache, csh)) + args, leaves


@pytest.mark.parametrize("which,tp,vocab,layers", [
    ("decode", 1, 50257, 12),
    ("prefill", 1, 50257, 12),
    # tp needs the vocabulary padded (50,257 divides by nothing); two
    # layers show what twelve do. 768 / 2 = 384 lanes a shard: whole tiles.
    ("decode", 2, 50304, 2),
    # 768 / 4 = 192 lanes a shard pad to 256, so the compiler stores the
    # shard block-minor again and converts it around the scatter: known,
    # priced in PERF.md, no cell runs it (ROADMAP B9).
    pytest.param("decode", 4, 50304, 2, marks=pytest.mark.xfail(
        strict=True, reason="192 lanes a shard do not fill 128-lane tiles")),
])
def test_paged_programs_touch_the_pool_in_place_on_v5e(topo, which, tp, vocab,
                                                       layers):
    jitted, args, leaves = _paged_program(topo, which, tp, vocab, layers)
    compiled = jitted.lower(*args).compile()
    memory = compiled.memory_analysis()
    leaf_elems = int(np.prod(leaves[0].shape)) // tp  # on one chip
    leaf_bytes = leaf_elems * leaves[0].dtype.itemsize
    # (a) the donation holds: every pool leaf's bytes are aliased to an output
    assert memory.alias_size_in_bytes >= len(leaves) * leaf_bytes
    # (b) nothing but the scatter (inside its fusion) makes a leaf-sized array
    made = {}
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                     r"([\w\-]+)\(", line)
        if m and np.prod([int(d) for d in m.group(1).split(",")]) == leaf_elems:
            made.setdefault(m.group(2), []).append(line.strip()[:160])
    for passive in ("parameter", "get-tuple-element", "bitcast", "tuple"):
        made.pop(passive, None)
    assert sorted(made) == ["fusion", "scatter"], {
        op: lines[0] for op, lines in made.items()}
    assert len(made["fusion"]) == len(made["scatter"]) == len(leaves)
    # (c) and the program's temporaries are smaller than one leaf
    assert memory.temp_size_in_bytes < leaf_bytes


# -- the same programs for the second block, at command-a-plus-tp8ep8's flags --
#
# `run serve --model command_a_plus_tp8ep8 --paged --kv-block-tokens 16
# --slots 64 --kv-pool-mb 2048 --max-context 16384 --prefill-chunk 512`:
# 8 pool leaves of 65,536 blocks, each [C, 16, 128] (one K/V head is one
# lane tile), a block table of 1,024 entries a row.
_MOE_CONTEXT, _MOE_POOL_BLOCKS, _MOE_WINDOW = 16384, 65536, 4096


def _moe_paged_program(topo, which):
    from distkeras_tpu.inference.generate import _decode_module
    from distkeras_tpu.models.cohere2_moe import command_a_plus_tp8ep8
    from distkeras_tpu.serving import engine

    table = _MOE_CONTEXT // _BLOCK_TOKENS
    module, cfg = _decode_module(
        command_a_plus_tp8ep8(), slots=True, paged_blocks=_MOE_POOL_BLOCKS,
        page_tokens=_BLOCK_TOKENS, page_table_blocks=table)
    assert cfg.sliding_window == _MOE_WINDOW
    abstract = jax.eval_shape(
        lambda r: module.init(r, jnp.zeros((_SLOTS, 1), jnp.int32),
                              train=False), jax.random.PRNGKey(0))
    rep = SingleDeviceSharding(topo.devices[0])

    def placed(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep), tree)

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    key = arg(jnp.uint32, 2)
    if which == "decode":
        fn = functools.partial(engine._paged_decode_fn, module, 0,
                               _MOE_POOL_BLOCKS)
        args = (arg(jnp.int32, _SLOTS), arg(jnp.float32, _SLOTS),
                arg(jnp.int32, _SLOTS), arg(jnp.int32, _SLOTS, table), key)
    else:  # one prefill chunk of 512 tokens
        fn = functools.partial(engine._paged_prefill_fn, module, 0)
        args = (arg(jnp.int32, 1, 512), arg(jnp.int32), arg(jnp.int32),
                arg(jnp.int32, table), arg(jnp.float32), key)
    leaves = [a for a in jax.tree.leaves(abstract["cache"]) if a.ndim > 1]
    return (jax.jit(fn, donate_argnums=(1,)),
            (placed(abstract["params"]), placed(abstract["cache"])) + args,
            leaves)


@pytest.mark.parametrize("which,rows,queries", [("decode", _SLOTS, 1),
                                                ("prefill", 1, 512)])
def test_window_layers_gather_their_window_and_no_more_on_v5e(topo, which,
                                                              rows, queries):
    """In the compiled step the three window layers gather the block-table
    entries that cover their window (4096 / 16 + 1 = 257 a decode row; 289
    for a chunk of 512 queries), the full layer all 1,024; the donated pool
    is written in place; parameters are bfloat16 (8.47 GB) and the program
    fits the chip."""
    from distkeras_tpu.ops.attention import window_table_entries

    jitted, args, leaves = _moe_paged_program(topo, which)
    assert len(leaves) == 8 and {l.shape for l in leaves} == {
        (_MOE_POOL_BLOCKS, _BLOCK_TOKENS, 128)}
    params = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                 for a in jax.tree.leaves(args[0]))
    assert round(params / 1e9, 2) == 8.47
    compiled = jitted.lower(*args).compile()
    memory = compiled.memory_analysis()
    leaf_bytes = int(np.prod(leaves[0].shape)) * leaves[0].dtype.itemsize
    assert memory.alias_size_in_bytes >= len(leaves) * leaf_bytes
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 13e9)  # of the chip's 16 GB
    entries = window_table_entries(_MOE_WINDOW, queries, _BLOCK_TOKENS)
    assert entries == {1: 257, 512: 289}[queries]
    lead = f"{rows}," if rows > 1 else ""
    gathered = re.findall(r"= bf16\[([\d,]+),16,128\]\S* gather\(",
                          compiled.as_text())
    assert sorted(gathered) == sorted(
        [f"{lead}{entries}"] * 6 + [f"{lead}{_MOE_CONTEXT // _BLOCK_TOKENS}"] * 2)
