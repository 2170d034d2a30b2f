"""Fault-tolerant checkpointing: npz shards + JSON manifest, tree
fingerprints (hash.tree -- the paper's family doing integrity duty, one
fused leaf launch per array plus a pytree root digest), atomic renames,
keep-last-k, latest-VALID resume, and elastic resharding on load.

Layout:
  <dir>/step_<n>.tmp/...   (written)   -> atomic rename to <dir>/step_<n>/
  <dir>/step_<n>/manifest.json         -- leaf paths, shapes, dtypes, fingerprints
  <dir>/step_<n>/arrays.npz            -- the data

Every array is stored UNSHARDED (gathered) with its logical PartitionSpec
recorded; restore re-shards onto whatever mesh is live (elastic scaling:
a restart with a different device count just builds a new mesh and loads).
For 1000+-node scale the same layout shards the npz per host -- the
manifest already carries per-leaf fingerprints so partial verification
works; single-process here writes one file.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import time
import zipfile

import jax
import jax.numpy as jnp
import numpy as np

from ..hash import fingerprint_bytes
from ..hash.tree import default_tree_hasher, root_of_leaf_fingerprints

# Manifest integrity scheme. "tree-v1" checkpoints carry per-leaf TREE
# digests (hash.tree: one fused leaf launch per array instead of the old
# serial per-chunk host loop) plus a pytree ROOT digest over (path, leaf_fp)
# pairs, so a manifest edit that swaps two intact leaves is also caught.
# The legacy "stream-v0" scheme (manifests without a "scheme" key) is
# RETIRED: verify/restore raise `UnsupportedManifestScheme`; run
# `migrate_legacy_manifest(step_dir)` once to upgrade in place.
_SCHEME_TREE = "tree-v1"
_SCHEME_LEGACY = "stream-v0"


class UnsupportedManifestScheme(RuntimeError):
    """The manifest's integrity scheme is no longer verifiable in-process.
    `stream-v0` support was removed one release after `tree-v1` landed;
    the bits on disk are fine -- upgrade the manifest offline with
    `repro.checkpoint.migrate_legacy_manifest(step_dir)`."""


def _leaf_fingerprint(arr: np.ndarray, scheme: str) -> int:
    """The integrity fingerprint of one stored array under `scheme` -- the
    single hashing helper both verify and restore go through."""
    if scheme != _SCHEME_TREE:
        raise UnsupportedManifestScheme(
            f"manifest scheme {scheme!r} is retired; only {_SCHEME_TREE!r} "
            "verifies. Upgrade once with "
            "repro.checkpoint.migrate_legacy_manifest(<step_dir>)")
    return default_tree_hasher().fingerprint_bytes(arr.tobytes())


def _leaf_path(kp) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)


def _flatten(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return [(_leaf_path(kp), leaf) for kp, leaf in flat], treedef


class CorruptCheckpointError(RuntimeError):
    """A checkpoint leaf failed its Multilinear integrity fingerprint."""


class Checkpointer:
    def __init__(self, directory: str, keep: int = 3):
        self.dir = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        # verify() results memoized per step, keyed on a stat signature of
        # the checkpoint files -- latest_valid() stops re-fingerprinting
        # every checkpoint on every call
        self._verify_cache: dict[int, tuple[tuple, bool]] = {}
        self._recover()

    def _recover(self) -> None:
        """Sweep crash debris from interrupted saves. A `step_N.old` next
        to a committed `step_N` is the replaced checkpoint whose delete
        never ran: remove it. A `step_N.old` with NO `step_N` means the
        crash hit between rename-aside and commit: rename it back (the old
        checkpoint is intact and is the best state we have). Orphaned
        `step_N.tmp` dirs are partial writes: drop them."""
        for name in os.listdir(self.dir):
            full = os.path.join(self.dir, name)
            if re.fullmatch(r"step_\d+\.tmp", name):
                shutil.rmtree(full, ignore_errors=True)
                continue
            m = re.fullmatch(r"(step_\d+)\.old", name)
            if m:
                final = os.path.join(self.dir, m.group(1))
                if os.path.exists(final):
                    shutil.rmtree(full, ignore_errors=True)
                else:
                    os.rename(full, final)

    # -- save ---------------------------------------------------------------

    def save(self, step: int, state) -> str:
        tmp = os.path.join(self.dir, f"step_{step}.tmp")
        final = os.path.join(self.dir, f"step_{step}")
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        flat, _ = _flatten(state)
        arrays = {}
        manifest = {"step": step, "time": time.time(),
                    "scheme": _SCHEME_TREE, "leaves": {}}
        pairs = []
        for i, (path, leaf) in enumerate(flat):
            arr = np.asarray(jax.device_get(leaf))
            if arr.dtype == jnp.bfloat16:
                arr = arr.astype(np.float32)
                stored_dtype = "bfloat16"
            else:
                stored_dtype = str(arr.dtype)
            key = f"a{i}"
            arrays[key] = arr
            fp = _leaf_fingerprint(arr, _SCHEME_TREE)
            pairs.append((path, fp))
            manifest["leaves"][path] = {
                "key": key,
                "shape": list(arr.shape),
                "dtype": stored_dtype,
                "fingerprint": f"{fp:016x}",
            }
        manifest["root"] = f"{root_of_leaf_fingerprints(pairs):016x}"
        np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        # commit with NO torn window: the previous version of this step is
        # renamed ASIDE (cheap, atomic) rather than deleted first, so a
        # crash at any point leaves either the old or the new checkpoint
        # restorable -- never neither. `_recover` sweeps the `.old` debris
        # a crash can leave behind.
        old = final + ".old"
        if os.path.exists(old):
            shutil.rmtree(old)
        if os.path.exists(final):
            os.rename(final, old)
        os.rename(tmp, final)  # atomic commit
        if os.path.exists(old):
            shutil.rmtree(old)
        self._verify_cache.pop(step, None)
        self._gc()
        return final

    def _gc(self):
        steps = sorted(self.steps())
        for s in steps[: -self.keep]:
            shutil.rmtree(os.path.join(self.dir, f"step_{s}"), ignore_errors=True)
            self._verify_cache.pop(s, None)

    # -- restore ------------------------------------------------------------

    def steps(self) -> list[int]:
        out = []
        for name in os.listdir(self.dir):
            m = re.fullmatch(r"step_(\d+)", name)
            if m:
                out.append(int(m.group(1)))
        return sorted(out)

    def _stat_sig(self, step: int) -> tuple | None:
        """(mtime_ns, size) signature of a checkpoint's files -- the verify
        cache key. None if the checkpoint is missing a file."""
        path = os.path.join(self.dir, f"step_{step}")
        try:
            return tuple(
                (fn, os.stat(os.path.join(path, fn)).st_mtime_ns,
                 os.stat(os.path.join(path, fn)).st_size)
                for fn in ("manifest.json", "arrays.npz"))
        except OSError:
            return None

    def verify(self, step: int) -> bool:
        """True iff every leaf fingerprint checks out. Results are cached
        per (step, file stat signature): repeated `latest_valid()` calls
        cost a couple of os.stat's, not a full re-fingerprint, and any
        on-disk change (rewrite, corruption with a size/mtime change)
        invalidates the cache entry."""
        sig = self._stat_sig(step)
        if sig is None:
            return False
        cached = self._verify_cache.get(step)
        if cached is not None and cached[0] == sig:
            return cached[1]
        ok = self._verify_uncached(step)
        self._verify_cache[step] = (sig, ok)
        return ok

    def _verify_uncached(self, step: int) -> bool:
        # Only unreadable or undecodable files count as corruption, so only
        # the reads sit in the handlers. Hashing runs outside them: an error
        # there (a fingerprint kernel that fails to lower, a retired
        # manifest scheme) propagates instead of passing for a corrupt file.
        unreadable = (OSError, KeyError, ValueError, zipfile.BadZipFile)
        path = os.path.join(self.dir, f"step_{step}")
        try:
            with open(os.path.join(path, "manifest.json")) as f:
                manifest = json.load(f)
            scheme = manifest.get("scheme", _SCHEME_LEGACY)
            leaves = manifest["leaves"]
            data = np.load(os.path.join(path, "arrays.npz"))
        except unreadable:
            return False
        pairs = []
        for leaf_path, meta in leaves.items():
            try:
                arr, want = data[meta["key"]], meta["fingerprint"]
            except unreadable:
                return False
            got = _leaf_fingerprint(arr, scheme)
            if f"{got:016x}" != want:
                return False
            pairs.append((leaf_path, got))
        if "root" in manifest:
            # pytree-level check: catches manifest edits that permute or
            # relabel individually-intact leaves
            root = root_of_leaf_fingerprints(pairs)
            if f"{root:016x}" != manifest["root"]:
                return False
        return True

    def latest_valid(self) -> int | None:
        """Newest checkpoint whose every fingerprint verifies -- corrupt or
        torn checkpoints (simulated node failure mid-write) are skipped.
        Un-migrated legacy checkpoints are skipped too (resume must keep
        working next to old debris), but only `migrate()` makes them
        restorable again."""
        for s in reversed(self.steps()):
            try:
                if self.verify(s):
                    return s
            except UnsupportedManifestScheme:
                continue
        return None

    def migrate(self, step: int) -> bool:
        """Upgrade one legacy checkpoint's manifest to tree-v1 in place
        (see `migrate_legacy_manifest`); True if a rewrite happened."""
        out = migrate_legacy_manifest(os.path.join(self.dir, f"step_{step}"))
        self._verify_cache.pop(step, None)
        return out

    def restore(self, step: int, like, mesh=None, fsdp_pods: bool = False):
        """Load into the structure of `like` (a state pytree or its specs).
        With `mesh`, arrays are placed with the rule-derived shardings --
        this is the elastic-rescale path (any mesh shape works)."""
        path = os.path.join(self.dir, f"step_{step}")
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        scheme = manifest.get("scheme", _SCHEME_LEGACY)
        data = np.load(os.path.join(path, "arrays.npz"))
        flat, treedef = jax.tree_util.tree_flatten_with_path(like)
        shardings = None
        if mesh is not None:
            from ..train.train_state import TrainState, state_shardings

            if isinstance(like, TrainState):
                shardings = state_shardings(like, mesh, fsdp_pods)
        sh_flat = jax.tree_util.tree_leaves(
            shardings, is_leaf=lambda x: hasattr(x, "spec")) if shardings else None
        out = []
        for i, (kp, leaf) in enumerate(flat):
            p = _leaf_path(kp)
            meta = manifest["leaves"][p]
            arr = data[meta["key"]]
            want = _leaf_fingerprint(arr, scheme)
            if f"{want:016x}" != meta["fingerprint"]:
                # a real error, not an assert: survives `python -O` and is
                # catchable by resume logic (fall back to latest_valid())
                raise CorruptCheckpointError(
                    f"step {step}: leaf {p!r} fingerprint mismatch "
                    f"(got {want:016x}, manifest {meta['fingerprint']})")
            if meta["dtype"] == "bfloat16":
                arr = arr.astype(jnp.bfloat16)
            if sh_flat is not None:
                out.append(jax.device_put(arr, sh_flat[i]))
            else:
                out.append(jnp.asarray(arr))
        return jax.tree_util.tree_unflatten(treedef, out)


def migrate_legacy_manifest(step_dir: str) -> bool:
    """Offline one-shot upgrade of a legacy `stream-v0` checkpoint to
    `tree-v1`: verify every leaf against its LEGACY streaming fingerprint
    (migration must not launder corruption), recompute tree-v1 per-leaf
    digests plus the pytree root, and atomically rewrite `manifest.json`.
    Returns True if a rewrite happened, False if already tree-v1. Raises
    `CorruptCheckpointError` if a legacy fingerprint does not match."""
    mpath = os.path.join(step_dir, "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    if manifest.get("scheme") == _SCHEME_TREE:
        return False
    data = np.load(os.path.join(step_dir, "arrays.npz"))
    pairs = []
    th = default_tree_hasher()
    for leaf_path, meta in manifest["leaves"].items():
        arr = data[meta["key"]]
        legacy = fingerprint_bytes(arr.tobytes())
        if f"{legacy:016x}" != meta["fingerprint"]:
            raise CorruptCheckpointError(
                f"{step_dir}: leaf {leaf_path!r} fails its legacy "
                f"stream-v0 fingerprint (got {legacy:016x}, manifest "
                f"{meta['fingerprint']}); refusing to migrate")
        fp = th.fingerprint_bytes(arr.tobytes())
        meta["fingerprint"] = f"{fp:016x}"
        pairs.append((leaf_path, fp))
    manifest["scheme"] = _SCHEME_TREE
    manifest["root"] = f"{root_of_leaf_fingerprints(pairs):016x}"
    tmp = mpath + ".tmp"
    with open(tmp, "w") as f:
        json.dump(manifest, f)
    os.replace(tmp, mpath)
    return True
