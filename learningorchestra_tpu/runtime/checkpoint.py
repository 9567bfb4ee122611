"""Checkpointing.

The reference has NO mid-training checkpointing — persistence is the
final artifact only, and a failed job is simply re-run from its stored
parent (SURVEY §5: binary_executor utils.py:195-208, server.py:74-118).
Here training jobs checkpoint per-epoch/step and can resume, and
pytree artifacts are serialized with msgpack (flax.serialization)
instead of pickles.

ONE layout on every backend: a directory per step holding msgpack
payload file(s) plus a manifest, committed through
:meth:`Checkpointer._commit_host`. The chip takes exactly the path the
CPU tests cover (atomic commit, manifest verification, quarantine,
async tier, shards); no tensorstore-backed reader shares a process
with jax's compilation cache.

Integrity (docs/RELIABILITY.md): each msgpack step dir carries a
``manifest.json`` (per-file byte size + sha256, step, wall time) and
is committed ATOMICALLY — payload and manifest are written and
fsynced into ``<step>.tmp/`` which one ``os.replace`` renames into
place, so a kill mid-save can never leave a half-written step that
``latest_step()`` would pick (leftover ``*.tmp`` dirs are swept on
init). ``restore()`` re-hashes the payload against the manifest;
a torn or bit-flipped step dir is moved to ``<dir>/.quarantine/``
(bounded to the ``LO_CKPT_QUARANTINE_KEEP`` newest entries) and
restore transparently falls back to the newest VERIFIED step.

Layout (``shards > 1``): the state dict is partitioned into N
byte-balanced sub-files (``shard-00000-of-00002.msgpack``, …) under
one merged manifest, so each mesh-slice shard can be written by its
owning host on a multi-host pod; every sub-file verifies
independently and restore merges them. ``shards == 1`` keeps the
single ``checkpoint.msgpack`` layout, byte-compatible with older
dirs.

The commit machinery is split so the async manager
(``runtime/async_ckpt.py``) can reuse it off the training thread:
``save()`` = device→host + ``_commit_host()``; the async worker
calls ``_commit_host()`` directly on an already-host-resident tree.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import time
import warnings
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import serialization

from learningorchestra_tpu.runtime import health as health_lib

_MSGPACK_NAME = "checkpoint.msgpack"
_MANIFEST_NAME = "manifest.json"
_QUARANTINE_DIR = ".quarantine"
_SHARD_PREFIX = "shard-"


def _quarantine_keep() -> int:
    """How many quarantined step dirs to retain (newest wins).
    Config-first so tests overriding Config see it; env fallback keeps
    the runtime layer importable standalone."""
    try:
        from learningorchestra_tpu.config import get_config

        return max(0, int(get_config().ckpt_quarantine_keep))
    except Exception:  # noqa: BLE001
        return max(0, int(os.environ.get(
            "LO_CKPT_QUARANTINE_KEEP", "4") or 4))


def _flatten_state(tree: Any, prefix: str = "") -> dict:
    """Flatten a nested state dict to ``{"a/b/c": leaf}``. Empty dict
    nodes survive as leaves (``from_state_dict`` requires every target
    key present, including ``model_state: {}``)."""
    if isinstance(tree, dict) and tree:
        out: dict = {}
        for key in tree:
            joined = f"{prefix}/{key}" if prefix else str(key)
            out.update(_flatten_state(tree[key], joined))
        return out
    return {prefix: tree}


def _unflatten_state(flat: dict) -> dict:
    out: dict = {}
    for key, value in flat.items():
        parts = key.split("/")
        node = out
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = value
    return out


def _leaf_nbytes(leaf: Any) -> int:
    try:
        return max(1, int(np.asarray(leaf).nbytes))
    except Exception:  # noqa: BLE001 — non-array leaf (e.g. {} node)
        return 1


class CheckpointCorrupted(IOError):
    """A step dir failed manifest verification (missing payload, size
    mismatch, sha256 mismatch, unreadable manifest). IOError subclass:
    if one ever escapes the fallback (explicit-step restore), the jobs
    layer classifies it transient."""


def _fsync_file(f) -> None:
    f.flush()
    os.fsync(f.fileno())


def _fsync_dir(path: str) -> None:
    # the rename itself must reach disk or a crash can forget a
    # committed step (POSIX: fsync the parent directory)
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:  # platforms without directory fds
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def _chaos_corrupt(path: str) -> None:
    """``ckpt_write:*:corrupt:<nbytes>`` chaos site: flip bytes of the
    just-written payload AFTER its checksum was taken — simulated bit
    rot that restore-side verification must catch. Lazy import: the
    runtime layer only touches services.faults when armed chaos specs
    are plausible, and never lets injection plumbing sink a save."""
    try:
        from learningorchestra_tpu.services import faults

        nbytes = faults.corrupt_nbytes("ckpt_write")
    except Exception:  # noqa: BLE001
        return
    if not nbytes:
        return
    size = os.path.getsize(path)
    nbytes = min(nbytes, size)
    with open(path, "r+b") as f:
        f.seek(size - nbytes)
        chunk = f.read(nbytes)
        f.seek(size - nbytes)
        f.write(bytes(b ^ 0xFF for b in chunk))
        _fsync_file(f)


def _place_like(restored: Any, target: Any) -> Any:
    """Put restored host leaves back onto the target's shardings."""

    def _place(leaf, tgt):
        if isinstance(tgt, jax.Array):
            return jax.device_put(
                jnp.asarray(leaf, tgt.dtype), tgt.sharding)
        return leaf

    return jax.tree_util.tree_map(_place, restored, target)


class Checkpointer:
    """save(step, pytree) / latest_step() / restore over the verified
    msgpack directory-per-step layout (module docstring)."""

    def __init__(self, directory: str, max_to_keep: int = 3,
                 shards: int = 1):
        self._dir = os.path.abspath(directory)
        os.makedirs(self._dir, exist_ok=True)
        self._max_to_keep = max_to_keep
        # sub-files per step commit (multi-host: one per mesh-slice
        # shard, i.e. shards=jax.process_count()); 1 = legacy layout
        self._shards = max(1, int(shards))
        # a kill mid-save leaves a <step>.tmp dir that was never
        # committed — it holds no verified state, sweep it
        for name in os.listdir(self._dir):
            if name.endswith(".tmp"):
                shutil.rmtree(os.path.join(self._dir, name),
                              ignore_errors=True)

    # -- msgpack layout helpers ----------------------------------------
    def _step_dirs(self) -> List[int]:
        steps = []
        for name in os.listdir(self._dir):
            if not name.isdigit():
                continue
            # sharded steps have no checkpoint.msgpack — the manifest
            # is the commit marker either way (legacy dirs keep the
            # payload-only check)
            step_dir = os.path.join(self._dir, name)
            if os.path.exists(os.path.join(step_dir, _MSGPACK_NAME)) \
                    or os.path.exists(
                        os.path.join(step_dir, _MANIFEST_NAME)):
                steps.append(int(name))
        return sorted(steps)

    def _step_path(self, step: int) -> str:
        return os.path.join(self._dir, str(step), _MSGPACK_NAME)

    def _manifest_path(self, step: int) -> str:
        return os.path.join(self._dir, str(step), _MANIFEST_NAME)

    def _load_manifest(self, step: int) -> Optional[dict]:
        """The step's manifest dict, None for a legacy (pre-manifest)
        dir, CheckpointCorrupted for an unreadable/malformed one."""
        path = self._manifest_path(step)
        if not os.path.exists(path):
            return None
        try:
            with open(path) as f:
                manifest = json.load(f)
        except (OSError, ValueError) as exc:
            raise CheckpointCorrupted(
                f"step {step}: unreadable manifest: {exc}") from exc
        if not isinstance(manifest, dict) or \
                not isinstance(manifest.get("files"), dict):
            raise CheckpointCorrupted(
                f"step {step}: malformed manifest (no files map)")
        return manifest

    def _verify_sizes(self, step: int) -> None:
        """Cheap (stat-only) verification against the manifest; legacy
        dirs with a payload pass. Raises CheckpointCorrupted."""
        manifest = self._load_manifest(step)
        if manifest is None:
            if not os.path.exists(self._step_path(step)):
                raise CheckpointCorrupted(f"step {step}: missing payload")
            return
        for name, meta in manifest["files"].items():
            path = os.path.join(self._dir, str(step), name)
            if not os.path.exists(path):
                raise CheckpointCorrupted(
                    f"step {step}: manifest names missing file {name!r}")
            size = os.path.getsize(path)
            if size != meta.get("bytes"):
                raise CheckpointCorrupted(
                    f"step {step}: {name} is {size} bytes, manifest "
                    f"says {meta.get('bytes')} (torn write?)")

    def _read_file_verified(self, step: int, name: str,
                            meta: dict) -> bytes:
        """One payload file's bytes, re-hashed against its manifest
        entry. Raises CheckpointCorrupted on any mismatch."""
        try:
            with open(os.path.join(self._dir, str(step), name),
                      "rb") as f:
                data = f.read()
        except OSError as exc:
            raise CheckpointCorrupted(
                f"step {step}: unreadable payload {name!r}: "
                f"{exc}") from exc
        if len(data) != meta.get("bytes"):
            raise CheckpointCorrupted(
                f"step {step}: {name} is {len(data)} bytes, "
                f"manifest says {meta.get('bytes')} (torn write?)")
        digest = hashlib.sha256(data).hexdigest()
        if digest != meta.get("sha256"):
            raise CheckpointCorrupted(
                f"step {step}: {name} sha256 {digest[:12]}… does "
                f"not match manifest {str(meta.get('sha256'))[:12]}… "
                f"(bit rot?)")
        return data

    def _read_verified_tree(self, step: int) -> Any:
        """The step's raw (nested) state dict, every manifest-listed
        sub-file re-hashed — the single- and sharded-layout read path.
        Raises CheckpointCorrupted; a legacy dir with no manifest is
        accepted as-is."""
        manifest = self._load_manifest(step)
        if manifest is None:
            try:
                with open(self._step_path(step), "rb") as f:
                    data = f.read()
            except OSError as exc:
                raise CheckpointCorrupted(
                    f"step {step}: unreadable payload: {exc}") from exc
            return serialization.msgpack_restore(data)
        shard_names = sorted(n for n in manifest["files"]
                             if n.startswith(_SHARD_PREFIX))
        try:
            if not shard_names:
                data = self._read_file_verified(
                    step, _MSGPACK_NAME,
                    manifest["files"].get(_MSGPACK_NAME, {}))
                return serialization.msgpack_restore(data)
            flat: dict = {}
            for name in shard_names:
                data = self._read_file_verified(
                    step, name, manifest["files"][name])
                part = serialization.msgpack_restore(data)
                if not isinstance(part, dict):
                    raise CheckpointCorrupted(
                        f"step {step}: {name} is not a shard map")
                flat.update(part)
            return _unflatten_state(flat)
        except CheckpointCorrupted:
            raise
        except Exception as exc:  # noqa: BLE001 — undecodable bytes
            raise CheckpointCorrupted(
                f"step {step}: undecodable payload: {exc}") from exc

    def _quarantine(self, step: int, reason: str) -> None:
        """Move a corrupt step dir aside (evidence over deletion) so
        latest_step()/restore() stop seeing it. The quarantine itself
        is BOUNDED — only the newest ``LO_CKPT_QUARANTINE_KEEP``
        entries survive, so repeated corruption under chaos cannot
        fill the disk."""
        src = os.path.join(self._dir, str(step))
        qdir = os.path.join(self._dir, _QUARANTINE_DIR)
        os.makedirs(qdir, exist_ok=True)
        dst = os.path.join(qdir, f"{step}-{int(time.time() * 1000)}")
        while os.path.exists(dst):
            dst += "x"
        try:
            os.replace(src, dst)
        except OSError:
            shutil.rmtree(src, ignore_errors=True)
        self._prune_quarantine(qdir)
        health_lib.record("quarantined")
        warnings.warn(
            f"quarantined checkpoint step {step} -> {dst}: {reason}",
            RuntimeWarning, stacklevel=3)

    @staticmethod
    def _prune_quarantine(qdir: str) -> None:
        keep = _quarantine_keep()
        try:
            entries = sorted(
                os.listdir(qdir),
                key=lambda n: os.path.getmtime(os.path.join(qdir, n)))
        except OSError:
            return
        for name in entries[:max(0, len(entries) - keep)]:
            shutil.rmtree(os.path.join(qdir, name), ignore_errors=True)

    def save(self, step: int, tree: Any) -> None:
        """Commit ``step`` (atomic; see module docstring). The commit
        wall clock — the training thread's checkpoint stall — is
        recorded as a ``checkpointCommit`` span on the current job
        trace and in the ``lo_checkpoint_commit_seconds`` histogram."""
        t0 = time.monotonic()
        try:
            self._save_impl(step, tree)
        finally:
            self._observe_commit(step, t0)

    @staticmethod
    def _observe_commit(step: int, t0: float) -> None:
        # lazy import, like _chaos_corrupt: the runtime layer must
        # stay importable without the services package
        try:
            from learningorchestra_tpu.observability import hist
            from learningorchestra_tpu.observability import trace

            end = time.monotonic()
            cur = trace.current()
            if cur is not None:
                trace.add("checkpointCommit", cur[0], t0, end,
                          parent=cur[1], step=int(step))
            hist.observe("lo_checkpoint_commit_seconds", end - t0)
        except Exception:  # noqa: BLE001 — observability is advisory
            pass

    def _save_impl(self, step: int, tree: Any) -> None:
        host = jax.tree_util.tree_map(np.asarray, tree)
        self._commit_host(step, host)

    def _shard_payloads(self, host: Any) -> dict:
        """``{file_name: payload_bytes}`` for one commit: a single
        msgpack blob, or N byte-balanced shard sub-files (greedy
        least-loaded bin packing over the flattened leaves, sorted by
        size then path — deterministic)."""
        state = serialization.to_state_dict(host)
        if self._shards <= 1 or not isinstance(state, dict) or not state:
            return {_MSGPACK_NAME: serialization.to_bytes(host)}
        flat = _flatten_state(state)
        n = min(self._shards, len(flat))
        bins: List[dict] = [{} for _ in range(n)]
        loads = [0] * n
        order = sorted(flat, key=lambda k: (-_leaf_nbytes(flat[k]), k))
        for key in order:
            i = loads.index(min(loads))
            bins[i][key] = flat[key]
            loads[i] += _leaf_nbytes(flat[key])
        return {
            f"{_SHARD_PREFIX}{i:05d}-of-{n:05d}.msgpack":
                serialization.msgpack_serialize(bins[i])
            for i in range(n)}

    def _commit_host(self, step: int, host: Any) -> None:
        """Atomically commit an already-host-resident pytree: stage
        the whole step dir, fsync contents, then one rename — a crash
        at any point leaves either the previous state or a .tmp dir
        the next init sweeps. This is the piece the async manager's
        background worker shares with the synchronous save path."""
        payloads = self._shard_payloads(host)
        final_dir = os.path.join(self._dir, str(step))
        tmp_dir = final_dir + ".tmp"
        shutil.rmtree(tmp_dir, ignore_errors=True)
        os.makedirs(tmp_dir)
        files = {}
        first_payload = None
        for name, data in payloads.items():
            path = os.path.join(tmp_dir, name)
            if first_payload is None:
                first_payload = path
            with open(path, "wb") as f:
                f.write(data)
                _fsync_file(f)
            files[name] = {"sha256": hashlib.sha256(data).hexdigest(),
                           "bytes": len(data)}
        manifest = {
            "step": int(step),
            "wallTime": time.time(),
            "files": files,
        }
        if first_payload is not None:
            _chaos_corrupt(first_payload)
        with open(os.path.join(tmp_dir, _MANIFEST_NAME), "w") as f:
            json.dump(manifest, f)
            _fsync_file(f)
        if os.path.exists(final_dir):
            shutil.rmtree(final_dir, ignore_errors=True)
        os.replace(tmp_dir, final_dir)
        _fsync_dir(self._dir)
        for old in self._step_dirs()[:-self._max_to_keep]:
            shutil.rmtree(os.path.join(self._dir, str(old)),
                          ignore_errors=True)

    def latest_step(self) -> Optional[int]:
        """Newest step passing cheap (size) verification. Steps failing
        it are skipped — not quarantined; only restore(), which does the
        full re-hash, moves dirs aside."""
        for step in reversed(self._step_dirs()):
            try:
                self._verify_sizes(step)
            except CheckpointCorrupted:
                continue
            return step
        return None

    def restore(self, target: Any, step: Optional[int] = None) -> Any:
        if step is not None:
            try:
                raw = self._read_verified_tree(step)
            except CheckpointCorrupted as exc:
                # an explicitly requested step has no substitute
                self._quarantine(step, str(exc))
                raise
            return self._decode(raw, target)
        # newest VERIFIED step: quarantine corrupt/torn dirs and fall
        # back until one passes (or none are left -> fresh start)
        while True:
            candidates = self._step_dirs()
            if not candidates:
                return None
            step = candidates[-1]
            try:
                raw = self._read_verified_tree(step)
            except CheckpointCorrupted as exc:
                self._quarantine(step, str(exc))
                continue
            return self._decode(raw, target)

    def _decode(self, raw: Any, target: Any) -> Any:
        host_target = jax.tree_util.tree_map(np.asarray, target)
        # raises ValueError on structural drift (missing/extra keys) —
        # same contract the engine's migration fallback keys off
        restored = serialization.from_state_dict(host_target, raw)
        for got, want in zip(jax.tree_util.tree_leaves(restored),
                             jax.tree_util.tree_leaves(host_target)):
            if np.shape(got) != np.shape(want):
                raise ValueError(
                    f"checkpoint leaf shape {np.shape(got)} does not "
                    f"match target shape {np.shape(want)}")
        return _place_like(restored, target)

    def saved_metadata(self, step: Optional[int] = None) -> Any:
        """The SAVED tree's structure as a pytree whose leaves carry
        shape/dtype — the layout-drift discriminator: comparing it
        structurally against the live state beats sniffing a restore
        error message, which rewords across releases."""
        if step is None:
            step = self.latest_step()
        if step is None:
            return None
        # raw nested state dict; numpy leaves expose .shape/.dtype
        return self._read_verified_tree(step)

    def restore_partial(self, target_subtree: Any,
                        step: Optional[int] = None) -> Any:
        """Restore only the subtrees named in ``target_subtree`` (e.g.
        params + step, skipping a drifted opt_state entirely, so the
        stale optimizer arrays are never grafted into the new state).
        Reads are VERIFIED like ``restore()``: a corrupt step is
        quarantined; with ``step=None`` the read falls back to the
        next-newest verified step, an explicit step raises."""
        while True:
            explicit = step is not None
            if not explicit:
                step = self.latest_step()
            if step is None:
                return None
            try:
                raw = self._read_verified_tree(step)
            except CheckpointCorrupted as exc:
                self._quarantine(step, str(exc))
                if explicit:
                    raise
                step = None
                continue
            break
        if not isinstance(raw, dict):
            return None
        out = {}
        for key, sub_target in target_subtree.items():
            if key not in raw:
                return None
            out[key] = serialization.from_state_dict(sub_target, raw[key])
        return out

    # -- sidecar progress metadata ------------------------------------
    # Epoch progress can't be reconstructed from the restored step when
    # a re-run reshapes the feed (different batch_size / data size), so
    # the engine records it here next to the step checkpoints.
    def save_meta(self, meta: dict) -> None:
        # atomic like a step commit (tmp + fsync + replace + parent
        # fsync): a crash mid-write must never leave a torn sidecar
        # that poisons resume
        path = os.path.join(self._dir, "progress.json")
        with open(path + ".tmp", "w") as f:
            json.dump(meta, f)
            _fsync_file(f)
        os.replace(path + ".tmp", path)
        _fsync_dir(self._dir)

    def load_meta(self) -> Optional[dict]:
        path = os.path.join(self._dir, "progress.json")
        if not os.path.exists(path):
            return None
        try:
            with open(path) as f:
                meta = json.load(f)
        except (OSError, ValueError):
            # a torn sidecar must not poison the restore path — step
            # checkpoints carry the real state; progress is best-effort
            return None
        return meta if isinstance(meta, dict) else None

    def wait_until_finished(self, reraise: bool = True) -> None:
        """Barrier for in-flight commits: none here (``save`` returns
        committed). Exists so callers treat this and the async manager
        (runtime/async_ckpt.py) uniformly."""
        del reraise

    def close(self) -> None:
        pass


# ----------------------------------------------------------------------
# msgpack pytree IO for artifact persistence (no pickle of jax arrays)
# ----------------------------------------------------------------------
def save_pytree(tree: Any, path: str) -> None:
    host_tree = jax.tree_util.tree_map(np.asarray, tree)
    with open(path, "wb") as f:
        f.write(serialization.to_bytes(host_tree))


def load_pytree(path: str, target: Any) -> Any:
    with open(path, "rb") as f:
        data = f.read()
    return serialization.from_bytes(target, data)
