"""Sharded checkpointing with elastic restore (DESIGN.md §6).

Format: one .npz per save holding every leaf (flattened tree paths as keys)
+ a JSON manifest (step, tree structure, shapes, dtypes). Restore accepts a
*different* mesh / device count: arrays are device_put with the new sharding
(elastic scaling after node loss). Writes are atomic (tmp + rename) and the
last K checkpoints are retained, so a crash mid-write never corrupts the
restore point — the checkpoint/restart fault-tolerance contract.

On a real multi-host pod each host writes only its addressable shards; here
the single-process container writes the full array (the format keeps a
`shards` field so the multi-host writer slots in without format changes).
"""
from __future__ import annotations

import json
import os
import re
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime import spans


def _flatten_with_paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for path, leaf in flat:
        key = "/".join(_path_str(p) for p in path)
        out[key] = leaf
    return out, treedef


def _path_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    return str(p)


def save(ckpt_dir: str, step: int, tree, keep: int = 3,
         meta: dict | None = None) -> str:
    """`meta`: optional JSON-serializable producer metadata stored in the
    manifest (e.g. the search engine records its backend family so a resume
    with an incompatible state layout fails with a clear error instead of a
    shape assertion — see repro.search.engine)."""
    os.makedirs(ckpt_dir, exist_ok=True)
    leaves, _ = _flatten_with_paths(tree)
    arrays = {k: np.asarray(jax.device_get(v)) for k, v in leaves.items()}
    # the host's own work, once the state has reached it
    with spans.span("checkpoint.write"):
        manifest = {
            "step": int(step),
            "keys": sorted(arrays.keys()),
            "shapes": {k: list(v.shape) for k, v in arrays.items()},
            "dtypes": {k: str(v.dtype) for k, v in arrays.items()},
            "shards": "full",
            "meta": meta or {},
        }
        final = os.path.join(ckpt_dir, f"ckpt_{step:08d}")
        with tempfile.TemporaryDirectory(dir=ckpt_dir) as tmp:
            np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
            with open(os.path.join(tmp, "manifest.json"), "w") as f:
                json.dump(manifest, f)
            os.makedirs(final + ".tmp", exist_ok=True)
            for name in ("arrays.npz", "manifest.json"):
                os.replace(os.path.join(tmp, name),
                           os.path.join(final + ".tmp", name))
        os.replace(final + ".tmp", final)  # atomic publish
        _gc(ckpt_dir, keep)
    return final


def read_manifest(ckpt_dir: str, step: int) -> dict:
    """The JSON manifest of one checkpoint (includes the `meta` dict)."""
    path = os.path.join(ckpt_dir, f"ckpt_{step:08d}", "manifest.json")
    with open(path) as f:
        return json.load(f)


def checkpoint_error(ckpt_dir: str, step: int) -> str | None:
    """Why `ckpt_<step>` cannot be restored, or None if it looks intact.

    Probes everything `restore` depends on without touching devices: the
    manifest must parse and carry its required fields, `arrays.npz` must
    open AND fully decompress (a truncated write fails on read, not on
    open), and every manifest key must be present in the archive.
    """
    path = os.path.join(ckpt_dir, f"ckpt_{step:08d}")
    try:
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        missing = [k for k in ("step", "keys", "shapes", "dtypes")
                   if k not in manifest]
        if missing:
            return f"manifest.json missing fields {missing}"
        with np.load(os.path.join(path, "arrays.npz")) as data:
            for key in manifest["keys"]:
                arr = data[key]   # forces decompression of the member
                if list(arr.shape) != list(manifest["shapes"][key]):
                    return (f"arrays.npz[{key!r}] shape {list(arr.shape)} "
                            f"!= manifest {manifest['shapes'][key]}")
    except Exception as e:  # corrupt JSON, truncated zip, missing member...
        return f"{type(e).__name__}: {e}"
    return None


def latest_step(ckpt_dir: str) -> int | None:
    """Newest *intact* checkpoint step, or None.

    A crash can leave a partially-written or corrupted `ckpt_<step>/`
    (e.g. a torn filesystem under the atomic-rename contract, or manual
    tampering); rather than letting the subsequent `restore` crash the
    resume, each candidate is verified newest-first with
    `checkpoint_error` and broken ones are skipped with a warning.
    """
    import warnings

    if not os.path.isdir(ckpt_dir):
        return None
    steps = sorted((int(m.group(1)) for d in os.listdir(ckpt_dir)
                    if (m := re.fullmatch(r"ckpt_(\d+)", d))), reverse=True)
    for step in steps:
        err = checkpoint_error(ckpt_dir, step)
        if err is None:
            return step
        warnings.warn(f"skipping unreadable checkpoint "
                      f"{ckpt_dir}/ckpt_{step:08d}: {err}")
    return None


def restore(ckpt_dir: str, step: int, like_tree, shardings=None):
    """Restore into the structure of `like_tree`.

    shardings: optional matching pytree of jax.sharding.Sharding — arrays are
    device_put with them (elastic restore onto a new mesh).
    """
    path = os.path.join(ckpt_dir, f"ckpt_{step:08d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    data = np.load(os.path.join(path, "arrays.npz"))
    leaves, treedef = _flatten_with_paths(like_tree)
    shard_leaves = None
    if shardings is not None:
        shard_leaves, _ = _flatten_with_paths(shardings)

    restored = {}
    for key, like in leaves.items():
        arr = data[key]
        assert list(arr.shape) == list(like.shape), (key, arr.shape, like.shape)
        target = jnp.asarray(arr, dtype=like.dtype)
        if shard_leaves is not None:
            target = jax.device_put(target, shard_leaves[key])
        restored[key] = target
    ordered = [restored[k] for k in leaves.keys()]
    return jax.tree_util.tree_unflatten(treedef, ordered), manifest["step"]


def _gc(ckpt_dir: str, keep: int):
    steps = sorted(
        int(m.group(1)) for d in os.listdir(ckpt_dir)
        if (m := re.fullmatch(r"ckpt_(\d+)", d)))
    for s in steps[:-keep]:
        import shutil
        shutil.rmtree(os.path.join(ckpt_dir, f"ckpt_{s:08d}"), ignore_errors=True)
