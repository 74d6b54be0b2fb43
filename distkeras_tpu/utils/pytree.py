"""PyTree arithmetic and (de)serialization helpers.

TPU-native replacement for the reference's model/weight plumbing
(``distkeras/utils.py`` § ``serialize_keras_model`` /
``deserialize_keras_model`` / ``pickle_object`` / ``unpickle_object``):
instead of pickled Keras JSON + weight lists we move PyTrees of ndarrays.
Serialization uses a self-describing, pickle-free npz container so frames
can cross process boundaries (the async PS transport) safely.
"""

from __future__ import annotations

import io
import json
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# PyTree arithmetic (the building blocks of every PS protocol update rule).
#
# Leaf-type dispatch: numpy inputs stay numpy (the PS loop runs on the HOST
# and must not bounce center weights through the accelerator on every
# commit); jax arrays stay jax (worker-side window math runs on device).
# ---------------------------------------------------------------------------


def _np_leaf(x) -> bool:
    return isinstance(x, np.ndarray) or np.isscalar(x)


def pytree_to_host(tree: Any) -> Any:
    """Materialize a PyTree as host numpy arrays, preserving leaf dtypes
    (param dtype must round-trip unchanged or jitted consumers retrace).
    The one shared host-materialization helper: the PS loop and the
    protocol layer must agree on it bit-for-bit."""
    return jax.tree.map(np.asarray, tree)


def pytree_add(a: Any, b: Any) -> Any:
    """``a + b`` leaf-wise."""
    return jax.tree.map(
        lambda x, y: np.add(x, y) if _np_leaf(x) and _np_leaf(y) else jnp.add(x, y),
        a,
        b,
    )


def pytree_sub(a: Any, b: Any) -> Any:
    """``a - b`` leaf-wise (e.g. weight deltas: ``w_after - w_before``)."""
    return jax.tree.map(
        lambda x, y: (
            np.subtract(x, y) if _np_leaf(x) and _np_leaf(y) else jnp.subtract(x, y)
        ),
        a,
        b,
    )


def pytree_scale(a: Any, s) -> Any:
    """``s * a`` leaf-wise."""
    return jax.tree.map(lambda x: x * s, a)


def pytree_zeros_like(a: Any) -> Any:
    return jax.tree.map(
        lambda x: np.zeros_like(x) if _np_leaf(x) else jnp.zeros_like(x), a
    )


def pytree_l2(tree: Any) -> float:
    """Whole-tree L2 norm ``sqrt(sum_leaves sum(x^2))`` as a host float.

    The ONE norm definition the training-health layer uses for update
    mass, divergence gauges, and goodput accounting — host numpy in
    float64 accumulation (bf16 wire trees upcast exactly), never a
    device dispatch: it runs inside the PS loop, which must not bounce
    through the accelerator. Non-numeric leaves are skipped."""
    import math

    total = 0.0
    for leaf in jax.tree.leaves(tree):
        try:
            a = np.asarray(leaf).astype(np.float64)
        except (TypeError, ValueError):
            continue
        a = a.ravel()
        total += float(a @ a)
    return math.sqrt(total)


def pytree_mean(trees: list[Any]) -> Any:
    """Arithmetic mean of a list of PyTrees (reference
    ``distkeras/trainers.py`` § ``AveragingTrainer`` semantics)."""
    if not trees:
        raise ValueError("pytree_mean of empty list")
    acc = trees[0]
    for t in trees[1:]:
        acc = pytree_add(acc, t)
    return pytree_scale(acc, 1.0 / len(trees))


# ---------------------------------------------------------------------------
# Serialization: PyTree -> bytes without pickle.
#
# Format: npz archive whose member names are "<index>" plus a JSON "treedef"
# member recording the tree structure via jax.tree.flatten key-paths.
# ---------------------------------------------------------------------------


def _treedef_to_json(tree: Any) -> str:
    paths = [
        "/".join(_key_str(k) for k in path)
        for path, _ in jax.tree.flatten_with_path(tree)[0]
    ]
    return json.dumps(paths)


def _key_str(key) -> str:
    # DictKey('a') -> "d:a", SequenceKey(0) -> "s:0", GetAttrKey -> "a:name"
    if isinstance(key, jax.tree_util.DictKey):
        return f"d:{key.key}"
    if isinstance(key, jax.tree_util.SequenceKey):
        return f"s:{key.idx}"
    if isinstance(key, jax.tree_util.GetAttrKey):
        return f"a:{key.name}"
    if isinstance(key, jax.tree_util.FlattenedIndexKey):
        return f"i:{key.key}"
    return f"r:{key!r}"


_WIDE_VIEW = {2: np.uint16, 1: np.uint8}


def serialize_pytree(tree: Any) -> bytes:
    """Serialize a PyTree of arrays to bytes (no pickle).

    Non-native dtypes (bfloat16, float8 — ml_dtypes) are stored as unsigned
    views with the true dtype recorded, since npz round-trips them as raw
    void data otherwise.
    """
    leaves, _ = jax.tree.flatten(tree)
    buf = io.BytesIO()
    arrays = {}
    dtypes = []
    for i, leaf in enumerate(leaves):
        arr = np.asarray(leaf)
        dtypes.append(arr.dtype.name if arr.dtype.names is None else str(arr.dtype))
        if arr.dtype.kind == "V" or arr.dtype.name not in np.sctypeDict:
            arr = np.ascontiguousarray(arr).view(_WIDE_VIEW[arr.dtype.itemsize])
        arrays[f"leaf_{i}"] = arr
    meta = json.dumps({"paths": json.loads(_treedef_to_json(tree)), "dtypes": dtypes})
    arrays["__treedef__"] = np.frombuffer(meta.encode("utf-8"), dtype=np.uint8)
    np.savez(buf, **arrays)
    return buf.getvalue()


def deserialize_pytree(data: bytes, like: Any | None = None) -> Any:
    """Inverse of :func:`serialize_pytree`.

    If ``like`` (a PyTree with the same structure) is given, the result is
    unflattened into that exact structure; otherwise a nested-dict tree is
    rebuilt from the recorded key paths.
    """
    with np.load(io.BytesIO(data)) as npz:
        n = sum(1 for k in npz.files if k.startswith("leaf_"))
        leaves = [npz[f"leaf_{i}"] for i in range(n)]
        meta = json.loads(bytes(npz["__treedef__"]).decode("utf-8"))
    if isinstance(meta, dict):
        paths, dtypes = meta["paths"], meta["dtypes"]
        leaves = [
            leaf.view(np.dtype(dt)) if leaf.dtype.name != dt else leaf
            for leaf, dt in zip(leaves, dtypes)
        ]
    else:  # legacy format: paths only
        paths = meta
    if like is not None:
        treedef = jax.tree.structure(like)
        return jax.tree.unflatten(treedef, leaves)
    # Rebuild nested dicts/lists from tagged paths ("d:name" dict key,
    # "s:idx" sequence index). The tag travels with the key so a dict whose
    # keys happen to be digits is never mistaken for a list.
    if len(leaves) == 1 and paths and paths[0] == "":
        return leaves[0]  # the tree was a bare leaf
    root: dict = {}
    for path_str, leaf in zip(paths, leaves):
        keys = path_str.split("/") if path_str else []
        node = root
        for j, ks in enumerate(keys):
            tag, name = ks[0], ks[2:]
            if j == len(keys) - 1:
                node[(tag, name)] = leaf
            else:
                node = node.setdefault((tag, name), {})

    def _fix(node):
        if not isinstance(node, dict):
            return node
        if node and all(t == "s" for t, _ in node):
            return [_fix(node[("s", str(i))]) for i in range(len(node))]
        return {name: _fix(v) for (_, name), v in node.items()}

    return _fix(root)
