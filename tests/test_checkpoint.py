"""Checkpointer: roundtrip, integrity (corruption detection), keep-k,
latest-valid resume, bfloat16 handling, torn-save crash recovery, typed
corruption errors, and the verify cache."""
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer, CorruptCheckpointError


def _state(seed=0):
    k = jax.random.key(seed)
    return {
        "params": {"w": jax.random.normal(k, (16, 8)),
                   "b16": jax.random.normal(k, (4,)).astype(jnp.bfloat16)},
        "opt": {"m": jnp.zeros((16, 8))},
        "step": jnp.asarray(7, jnp.int32),
    }


def test_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path))
    st = _state()
    ck.save(7, st)
    assert ck.steps() == [7]
    assert ck.verify(7)
    out = ck.restore(7, jax.tree.map(lambda x: jnp.zeros_like(x), st))
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a).astype(np.float32),
                                      np.asarray(b).astype(np.float32))
    assert out["params"]["b16"].dtype == jnp.bfloat16


def test_corruption_detected(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(1, _state(1))
    ck.save(2, _state(2))
    # corrupt the newest arrays file
    path = os.path.join(str(tmp_path), "step_2", "arrays.npz")
    data = bytearray(open(path, "rb").read())
    data[len(data) // 2] ^= 0xFF
    open(path, "wb").write(bytes(data))
    assert not ck.verify(2)
    assert ck.verify(1)
    assert ck.latest_valid() == 1  # resume skips the corrupt checkpoint


def test_keep_k(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        ck.save(s, _state(s))
    assert ck.steps() == [3, 4]


def test_manifest_contents(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(5, _state())
    with open(os.path.join(str(tmp_path), "step_5", "manifest.json")) as f:
        man = json.load(f)
    assert man["step"] == 5
    assert "params/w" in man["leaves"]
    for meta in man["leaves"].values():
        assert len(meta["fingerprint"]) == 16  # 64-bit multilinear fp


def test_restore_wrong_structure_fails(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(1, _state())
    with pytest.raises(KeyError):
        ck.restore(1, {"different": jnp.zeros(3)})


def test_crash_at_commit_keeps_old_checkpoint(tmp_path, monkeypatch):
    """Simulated node failure at the tmp->final rename: the PREVIOUS
    version of the step must survive (the old save flow deleted it before
    committing -- a crash in that window lost both)."""
    ck = Checkpointer(str(tmp_path))
    st_old = _state(1)
    ck.save(3, st_old)
    real_rename = os.rename

    def crashing_rename(src, dst):
        if str(src).endswith(".tmp"):
            raise OSError("simulated crash at commit")
        return real_rename(src, dst)

    with monkeypatch.context() as m:
        m.setattr(os, "rename", crashing_rename)
        with pytest.raises(OSError, match="simulated crash"):
            ck.save(3, _state(2))
    # a fresh process opens the directory: recovery sweeps the debris
    ck2 = Checkpointer(str(tmp_path))
    assert ck2.steps() == [3]
    assert ck2.verify(3)
    assert not any(n.endswith((".tmp", ".old"))
                   for n in os.listdir(str(tmp_path)))
    out = ck2.restore(3, jax.tree.map(lambda x: jnp.zeros_like(x), st_old))
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]),
                                  np.asarray(st_old["params"]["w"]))


def test_crash_after_commit_sweeps_old_debris(tmp_path):
    """Crash AFTER the commit rename but before the .old delete: the new
    checkpoint wins and the stale copy is swept on next open."""
    ck = Checkpointer(str(tmp_path))
    st = _state(4)
    ck.save(2, st)
    src = os.path.join(str(tmp_path), "step_2")
    shutil.copytree(src, src + ".old")  # fabricate the mid-crash layout
    ck2 = Checkpointer(str(tmp_path))
    assert ck2.steps() == [2]
    assert not os.path.exists(src + ".old")
    out = ck2.restore(2, jax.tree.map(lambda x: jnp.zeros_like(x), st))
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]),
                                  np.asarray(st["params"]["w"]))


def test_restore_corrupt_raises_typed_error(tmp_path):
    ck = Checkpointer(str(tmp_path))
    st = _state()
    ck.save(1, st)
    man_path = os.path.join(str(tmp_path), "step_1", "manifest.json")
    with open(man_path) as f:
        man = json.load(f)
    leaf = next(iter(man["leaves"]))
    man["leaves"][leaf]["fingerprint"] = "0" * 16
    with open(man_path, "w") as f:
        json.dump(man, f)
    with pytest.raises(CorruptCheckpointError, match="fingerprint mismatch"):
        ck.restore(1, jax.tree.map(lambda x: jnp.zeros_like(x), st))


def test_verify_cache_skips_refingerprint(tmp_path, monkeypatch):
    from repro.checkpoint import checkpointer as ckpt_mod

    ck = Checkpointer(str(tmp_path))
    ck.save(1, _state(1))
    ck.save(2, _state(2))
    calls = {"n": 0}
    real_fp = ckpt_mod._leaf_fingerprint

    def counting_fp(arr, scheme):
        calls["n"] += 1
        return real_fp(arr, scheme)

    monkeypatch.setattr(ckpt_mod, "_leaf_fingerprint", counting_fp)
    assert ck.latest_valid() == 2
    first = calls["n"]
    assert first > 0
    assert ck.latest_valid() == 2
    assert calls["n"] == first  # cache hit: a stat, not a re-fingerprint
    # an on-disk change invalidates the cached verdict
    path = os.path.join(str(tmp_path), "step_2", "arrays.npz")
    data = bytearray(open(path, "rb").read())
    data[len(data) // 2] ^= 0xFF
    open(path, "wb").write(bytes(data))
    assert ck.latest_valid() == 1
    assert calls["n"] > first


# ---------------------------------------------------------------------------
# tree-v1 integrity scheme (hash.tree) + legacy manifest compatibility
# ---------------------------------------------------------------------------

def test_manifest_carries_tree_scheme_and_root(tmp_path):
    from repro.hash.tree import default_tree_hasher, root_of_leaf_fingerprints

    ck = Checkpointer(str(tmp_path))
    ck.save(1, _state())
    with open(os.path.join(str(tmp_path), "step_1", "manifest.json")) as f:
        man = json.load(f)
    assert man["scheme"] == "tree-v1"
    th = default_tree_hasher()
    data = np.load(os.path.join(str(tmp_path), "step_1", "arrays.npz"))
    pairs = []
    for path, meta in man["leaves"].items():
        fp = th.fingerprint_bytes(data[meta["key"]].tobytes())
        assert meta["fingerprint"] == f"{fp:016x}", path
        pairs.append((path, fp))
    assert man["root"] == f"{root_of_leaf_fingerprints(pairs):016x}"


def test_root_digest_catches_manifest_leaf_swap(tmp_path):
    """Two individually-intact leaves swapped in the manifest: every
    per-leaf fingerprint still matches its (relabeled) array, so only the
    pytree root catches it."""
    ck = Checkpointer(str(tmp_path))
    ck.save(1, {"a": jnp.zeros((4,)), "b": jnp.ones((4,))})
    assert ck.verify(1)
    man_path = os.path.join(str(tmp_path), "step_1", "manifest.json")
    with open(man_path) as f:
        man = json.load(f)
    a, b = man["leaves"]["a"], man["leaves"]["b"]
    man["leaves"]["a"], man["leaves"]["b"] = b, a
    with open(man_path, "w") as f:
        json.dump(man, f)
    ck._verify_cache.clear()
    assert not ck.verify(1)


def _legacy_rewrite(step: str) -> None:
    """Rewrite a committed step dir as a legacy stream-v0 checkpoint:
    streaming fingerprints, no scheme/root keys."""
    from repro.hash import fingerprint_bytes

    with open(os.path.join(step, "manifest.json")) as f:
        man = json.load(f)
    data = np.load(os.path.join(step, "arrays.npz"))
    man.pop("scheme"); man.pop("root")
    for path, meta in man["leaves"].items():
        meta["fingerprint"] = \
            f"{fingerprint_bytes(data[meta['key']].tobytes()):016x}"
    with open(os.path.join(step, "manifest.json"), "w") as f:
        json.dump(man, f)


def test_legacy_manifest_raises_typed_error_and_migrates(tmp_path):
    """stream-v0 is retired: verify/restore raise `UnsupportedManifestScheme`
    (pointing at the migration helper, never a silent False), latest_valid
    skips the un-migrated checkpoint, and one `migrate()` round-trips it
    back to fully verifiable tree-v1 -- bit-identical restore."""
    from repro.checkpoint import UnsupportedManifestScheme

    ck = Checkpointer(str(tmp_path))
    st = _state()
    ck.save(1, st)
    tree_man = json.load(
        open(os.path.join(str(tmp_path), "step_1", "manifest.json")))
    ck.save(2, st)
    step2 = os.path.join(str(tmp_path), "step_2")
    _legacy_rewrite(step2)
    ck._verify_cache.clear()
    with pytest.raises(UnsupportedManifestScheme, match="tree-v1"):
        ck.verify(2)
    with pytest.raises(UnsupportedManifestScheme, match="migrate"):
        ck.restore(2, jax.tree.map(lambda x: jnp.zeros_like(x), st))
    # resume survives legacy debris: the newest VERIFIABLE step wins
    assert ck.latest_valid() == 1
    # offline migration: legacy-verify -> tree-v1 rewrite, then everything
    # works again and the manifest equals a native tree-v1 save's
    assert ck.migrate(2)
    assert not ck.migrate(2)  # idempotent: already tree-v1
    assert ck.verify(2) and ck.latest_valid() == 2
    out = ck.restore(2, jax.tree.map(lambda x: jnp.zeros_like(x), st))
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]),
                                  np.asarray(st["params"]["w"]))
    man = json.load(open(os.path.join(step2, "manifest.json")))
    assert man["scheme"] == "tree-v1"
    assert man["root"] == tree_man["root"]
    assert ({p: m["fingerprint"] for p, m in man["leaves"].items()}
            == {p: m["fingerprint"] for p, m in tree_man["leaves"].items()})


def test_migration_refuses_corrupt_legacy_checkpoint(tmp_path):
    """Migration must not launder corruption into a fresh tree-v1 manifest:
    a byte flip under a legacy manifest fails the LEGACY fingerprint check
    and the manifest is left untouched."""
    ck = Checkpointer(str(tmp_path))
    ck.save(1, _state())
    step = os.path.join(str(tmp_path), "step_1")
    _legacy_rewrite(step)
    # corrupt one array IN PLACE (clean zip, wrong bytes): the legacy
    # fingerprint check must catch it, not a zipfile CRC error
    npz = os.path.join(step, "arrays.npz")
    data = dict(np.load(npz))
    data["a0"] = data["a0"].copy()
    data["a0"].reshape(-1)[0] += 1
    np.savez(npz, **data)
    with pytest.raises(CorruptCheckpointError, match="stream-v0"):
        ck.migrate(1)
    assert "scheme" not in json.load(
        open(os.path.join(step, "manifest.json")))


def test_leaf_fingerprint_rejects_retired_scheme():
    """tree-v1 equals hash.tree's fingerprint_bytes exactly; any other
    scheme string is a typed error, not a silent fallback."""
    from repro.checkpoint import UnsupportedManifestScheme
    from repro.checkpoint.checkpointer import _leaf_fingerprint
    from repro.hash.tree import default_tree_hasher

    arr = np.arange(1024, dtype=np.float32)
    assert _leaf_fingerprint(arr, "tree-v1") == \
        default_tree_hasher().fingerprint_bytes(arr.tobytes())
    for scheme in ("stream-v0", "banana-v9"):
        with pytest.raises(UnsupportedManifestScheme):
            _leaf_fingerprint(arr, scheme)


@pytest.mark.parametrize("exc", [RuntimeError, ValueError])
def test_fingerprint_failure_propagates_not_corrupt(tmp_path, monkeypatch, exc):
    """A hashing failure (say, a fingerprint kernel that fails to lower) is
    an error, not a corrupt checkpoint: verify/latest_valid raise it instead
    of silently resuming from nothing -- even when its type is one a
    corrupt file's read could also raise."""
    from repro.checkpoint import checkpointer as ckmod

    ck = Checkpointer(str(tmp_path))
    ck.save(1, _state(1))

    def broken(arr, scheme):
        raise exc("Mosaic failed to compile TPU kernel (simulated)")

    monkeypatch.setattr(ckmod, "_leaf_fingerprint", broken)
    with pytest.raises(exc, match="Mosaic"):
        ck.verify(1)
    with pytest.raises(exc, match="Mosaic"):
        ck.latest_valid()
    monkeypatch.undo()
    assert ck.verify(1) and ck.latest_valid() == 1  # nothing was cached


def test_unreadable_files_still_count_as_corrupt(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(1, _state(1))
    with open(os.path.join(str(tmp_path), "step_1", "manifest.json"), "w") as f:
        f.write("{not json")
    assert not ck.verify(1)
    ck.save(2, _state(2))
    open(os.path.join(str(tmp_path), "step_2", "arrays.npz"), "wb").write(
        b"PK\x03\x04 truncated")
    assert not ck.verify(2)
    assert ck.latest_valid() is None
