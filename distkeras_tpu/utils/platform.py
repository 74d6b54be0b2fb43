"""Platform selection and the compile cache.

Platform selection belongs to JAX: the default is whatever backend JAX
finds (the TPU on a chip host), ``JAX_PLATFORMS`` in the environment
overrides it, and ``cpu`` is for tests and virtual-device meshes. The
helpers here only cover what an environment variable cannot: choosing a
platform from a script's own ``--platform`` flag, before the first
backend-initializing call, and asking the CPU backend for N virtual
devices. Nothing here falls back from one platform to another.

One process owns a chip: a parent that initialized a backend holds it, and
a child that needs it then fails or hangs. :func:`jax_backends_live` is the
check launchers use to stay off the device.

No reference analogue (the reference picks backends via Spark executor
placement).
"""

from __future__ import annotations

import os
import re
import sys


def jax_backends_live() -> bool:
    """True iff jax has already initialized at least one backend.

    Uses the private ``xla_bridge._backends`` registry; degrades to False
    (the safe "not yet initialized" answer) if that moves in a future jax.
    """
    if sys.modules.get("jax") is None:
        return False
    try:
        from jax._src import xla_bridge

        return bool(xla_bridge._backends)
    except Exception:
        return False


def ensure_virtual_cpu_flags(n: int) -> None:
    """Request >=n virtual host CPU devices via XLA_FLAGS.

    Only effective before jax initializes backends; appends or raises the
    ``--xla_force_host_platform_device_count`` flag as needed.

    Also forces single-threaded Eigen kernels: the virtual devices share
    ONE intra-op thread pool, and a collective program whose per-partition
    compute contains pool-parallel Eigen ops (matmuls past Eigen's
    inline-execution threshold, e.g. a 500-wide MLP) can deadlock — the
    partitions already blocked inside the all-reduce rendezvous occupy the
    pool while the last partition's matmul waits for pool capacity, and
    XLA's 40s rendezvous termination kills the process. Single-threaded
    Eigen makes every partition's compute self-contained. Real TPUs don't
    share an intra-op pool across chips; this is simulation-only plumbing.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        flags = (flags + f" --xla_force_host_platform_device_count={n}").strip()
    elif int(m.group(1)) < n:
        flags = flags.replace(
            m.group(0), f"--xla_force_host_platform_device_count={n}"
        )
    if n > 1 and "--xla_cpu_multi_thread_eigen" not in flags:
        flags += " --xla_cpu_multi_thread_eigen=false"
    os.environ["XLA_FLAGS"] = flags


def force_platform(platform: str | None, num_virtual_cpu: int | None = None) -> None:
    """Pin jax to ``platform`` ("cpu", "tpu", or None for JAX's default).

    The name goes to ``jax_platforms`` as given — ``"tpu"`` means the TPU
    and nothing else, so a host without one fails at backend init instead
    of quietly running on the CPU. Must run before jax initializes any
    backend; raises RuntimeError if one is already live
    (``jax.config.update("jax_platforms", ...)`` silently no-ops after
    init, which would leave the script on whatever platform came first).

    With ``platform="cpu"``, ``num_virtual_cpu`` alone implies cpu; N
    virtual host devices are requested for mesh work on a machine without
    N real chips.
    """
    if num_virtual_cpu and platform in (None, "", "default"):
        platform = "cpu"
    if platform in (None, "", "default"):
        return
    if jax_backends_live():
        raise RuntimeError(
            f"cannot force platform {platform!r}: jax already initialized a "
            "backend (jax.config.update('jax_platforms', ...) would silently "
            "no-op). Call force_platform before any jax.devices()/jnp use."
        )
    if platform == "cpu" and num_virtual_cpu:
        ensure_virtual_cpu_flags(num_virtual_cpu)
    elif platform == "cpu":
        # Virtual devices may come from a pre-set XLA_FLAGS env instead of
        # num_virtual_cpu — the Eigen single-threading (see
        # ensure_virtual_cpu_flags) must cover that route too, or the
        # collective-rendezvous deadlock it prevents stays live.
        flags = os.environ.get("XLA_FLAGS", "")
        m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
        if m and int(m.group(1)) > 1:
            ensure_virtual_cpu_flags(int(m.group(1)))
    import jax

    jax.config.update("jax_platforms", platform)


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one place and
    return that directory.

    ``JAX_COMPILATION_CACHE_DIR`` in the environment wins and nothing is
    touched — JAX reads that variable itself. Otherwise the cache is
    ``<checkout>/.jax_cache`` (git-ignored): a fixed path, because the
    path is part of how a cache is found again — a directory that moves
    with ``TMPDIR``, a pid or the clock never hits. Every process of the
    repo (``run.py`` and its replica children, ``bench.py``,
    ``chip_smoke.py``, the tests) calls this and sets no other cache
    directory. Does not initialize a backend.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(checkout, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def add_platform_flag(parser) -> None:
    """Add ``--platform`` / ``--devices`` to an example's argparse parser."""
    parser.add_argument(
        "--platform", default=None, choices=["cpu", "tpu", "default"],
        help="Pin the jax platform (default: whatever JAX finds, or the "
        "JAX_PLATFORMS env var; 'tpu' fails without a chip, it never "
        "falls back to cpu).")
    parser.add_argument(
        "--devices", type=int, default=None,
        help="Number of virtual host devices (implies --platform cpu).")


def apply_platform_args(args) -> None:
    force_platform(getattr(args, "platform", None),
                   getattr(args, "devices", None))
