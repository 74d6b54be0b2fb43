"""The main path's Pallas kernels, compiled for a TPU that is described and
not attached (``v5e:2x2``), at the widths chip_smoke.py runs them.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: a slice off the tiling, too much fast memory, a kernel
that cannot be partitioned. These compiles can, at no chip time — each
asserts the Mosaic kernel is really in the executable
(``tpu_custom_call``), i.e. nothing fell to interpret. Nothing runs: a
compile that passes says nothing about results or speed.
"""

import os

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
