"""Sharded checkpointing with atomic commits, async writes, and
content integrity.

Layout per step::

    <dir>/step_<n>.tmp/   -> written, fsync'd, then os.replace ->
    <dir>/step_<n>/
        manifest.json     # schema, treedef, shapes, dtypes, step, checksum
        arrays.npz        # flattened leaves keyed by path

Restore rebuilds the pytree and (optionally) re-device_puts every leaf
onto a *different* mesh/sharding — that is the elastic-restart path: a
job that lost a pod restores the same checkpoint onto the smaller mesh.

Integrity: the manifest carries a ``schema`` version and the SHA-256 of
``arrays.npz``. Restore verifies both BEFORE any leaf is parsed and
raises :class:`CheckpointCorruptError` on a truncated, bit-flipped or
incompatibly-versioned checkpoint — resuming a multi-hour streaming run
from silently corrupted state would poison every step after it, so a
bad file must fail loudly at the resume boundary. The atomic-commit
protocol makes corruption unlikely (a torn write never lands on the
final path); the checksum covers what the protocol cannot: storage
rot, partial copies between machines, and human edits.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import threading
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import span

_SEP = "/"

# Bump on any incompatible change to the on-disk layout. Version 1 =
# the original (manifest without integrity fields); absent fields are
# treated as version 1, so pre-upgrade checkpoints still restore.
SCHEMA_VERSION = 2


class CheckpointCorruptError(RuntimeError):
    """A checkpoint failed integrity verification: truncated/bit-flipped
    payload (checksum mismatch), unreadable manifest, or a schema
    version this code does not understand. Do NOT resume from it —
    delete the step directory (or the whole checkpoint dir) and restart
    from the previous good step or from scratch."""


def _flatten(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for path, leaf in flat:
        key = _SEP.join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        out[key] = np.asarray(leaf)
    return out, treedef


def _sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            h.update(block)
    return h.hexdigest()


class Checkpointer:
    def __init__(self, directory: str, keep: int = 3):
        self.dir = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._thread: Optional[threading.Thread] = None

    # -- save ---------------------------------------------------------
    def save(self, step: int, tree: Any, blocking: bool = True,
             meta: Optional[dict] = None) -> str:
        """Snapshot on the caller thread, write (optionally) async.

        ``meta`` is an optional JSON-serialisable dict stored verbatim
        in the manifest (``manifest["meta"]``) — run provenance, config
        hashes, recorder cursors. It is observability payload only:
        restore ignores it entirely, so old readers and version-2
        manifests without the key are unaffected.
        """
        arrays, _ = _flatten(tree)
        manifest = {
            "schema": SCHEMA_VERSION,
            "step": int(step),
            "keys": {k: {"shape": list(v.shape), "dtype": str(v.dtype)}
                     for k, v in arrays.items()},
        }
        if meta is not None:
            manifest["meta"] = meta

        @span("sim.checkpoint_write")
        def write():
            final = os.path.join(self.dir, f"step_{step:08d}")
            tmp = final + ".tmp"
            if os.path.exists(tmp):
                shutil.rmtree(tmp)
            os.makedirs(tmp)
            npz = os.path.join(tmp, "arrays.npz")
            np.savez(npz, **arrays)
            # checksum the bytes as they landed on disk, not the
            # in-memory arrays: it must catch whatever happens to the
            # file after this point
            manifest["checksum"] = "sha256:" + _sha256(npz)
            with open(os.path.join(tmp, "manifest.json"), "w") as f:
                json.dump(manifest, f)
                f.flush()
                os.fsync(f.fileno())
            if os.path.exists(final):
                shutil.rmtree(final)
            os.replace(tmp, final)          # atomic commit
            self._gc()

        self.wait()
        if blocking:
            write()
        else:
            self._thread = threading.Thread(target=write, daemon=True)
            self._thread.start()
        return os.path.join(self.dir, f"step_{step:08d}")

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _gc(self):
        steps = self.all_steps()
        for s in steps[:-self.keep]:
            shutil.rmtree(os.path.join(self.dir, f"step_{s:08d}"),
                          ignore_errors=True)

    # -- restore ------------------------------------------------------
    def all_steps(self):
        out = []
        for name in os.listdir(self.dir):
            if name.startswith("step_") and not name.endswith(".tmp"):
                out.append(int(name.split("_")[1]))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def verify(self, step: int) -> dict:
        """Integrity-check one step's files; returns the manifest.

        Raises :class:`CheckpointCorruptError` on an unreadable
        manifest, an unsupported schema version, or an ``arrays.npz``
        whose SHA-256 does not match the recorded checksum. Version-1
        checkpoints (written before the integrity header existed) have
        no checksum to verify and pass with a manifest-only check.
        """
        path = os.path.join(self.dir, f"step_{step:08d}")
        try:
            with open(os.path.join(path, "manifest.json")) as f:
                manifest = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            raise CheckpointCorruptError(
                f"checkpoint {path}: unreadable manifest ({e}); delete "
                f"the step directory and resume from an earlier step"
            ) from e
        schema = manifest.get("schema", 1)
        if not isinstance(schema, int) or schema > SCHEMA_VERSION:
            raise CheckpointCorruptError(
                f"checkpoint {path}: schema version {schema!r} is newer "
                f"than this code understands (<= {SCHEMA_VERSION}); "
                f"upgrade the code or re-create the checkpoint")
        recorded = manifest.get("checksum")
        if recorded is not None:
            npz = os.path.join(path, "arrays.npz")
            try:
                actual = "sha256:" + _sha256(npz)
            except OSError as e:
                raise CheckpointCorruptError(
                    f"checkpoint {path}: cannot read arrays.npz ({e})"
                ) from e
            if actual != recorded:
                raise CheckpointCorruptError(
                    f"checkpoint {path}: arrays.npz checksum mismatch "
                    f"(manifest {recorded}, file {actual}) — the "
                    f"payload is truncated or bit-flipped; delete the "
                    f"step directory and resume from an earlier step")
        return manifest

    def restore(self, template: Any, step: Optional[int] = None,
                shardings: Any = None):
        """Rebuild `template`'s structure from disk.

        ``shardings`` (same structure, NamedSharding leaves) re-places
        every leaf — pass the *new* mesh's shardings for elastic restore.
        Verifies the step's integrity header first (see ``verify``).
        """
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        self.verify(step)
        path = os.path.join(self.dir, f"step_{step:08d}")
        data = np.load(os.path.join(path, "arrays.npz"))
        flat, treedef = jax.tree_util.tree_flatten_with_path(template)
        leaves = []
        for kpath, leaf in flat:
            key = _SEP.join(str(getattr(p, "key", getattr(p, "idx", p)))
                            for p in kpath)
            arr = data[key]
            leaves.append(arr)
        tree = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(template), leaves)
        if shardings is not None:
            tree = jax.tree.map(
                lambda x, s: jax.device_put(jnp.asarray(x), s),
                tree, shardings)
        else:
            tree = jax.tree.map(jnp.asarray, tree)
        return tree, step
