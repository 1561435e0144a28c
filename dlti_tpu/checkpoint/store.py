"""Crash-consistent train-state checkpoint store.

Replaces the Orbax/tensorstore backend for *train-state* checkpoints with
an in-tree store built around an explicit atomic-finalize protocol, so
every failure mode has a defined, tested recovery:

* **Atomic commit.** A save writes everything into a ``.tmp-<step>-*``
  staging dir (array shards, sidecar, ``MANIFEST.json`` with per-file
  SHA-256 digests, then a ``COMMIT`` marker carrying the manifest's own
  digest, in that order, each fsynced), and only then renames the staging
  dir to the bare-integer step dir. A reader can never observe a
  half-written committed checkpoint: a kill mid-save leaves a ``.tmp-*``
  dir that the resume scan quarantines.
* **Verified resume.** ``latest_verified_step`` / ``restore_latest_verified``
  walk committed steps newest-first, re-hash every file against the
  manifest, and *quarantine* (rename into ``_quarantine/``, count, log)
  anything incomplete or corrupt — truncated files, bit flips, missing
  commit markers — falling back to the newest checkpoint that proves out
  instead of crashing.
* **Bounded retry.** Transient write failures retry with exponential
  backoff (``dlti_ckpt_save_retries``); a save that exhausts its retries
  logs loudly and training continues (a failed save must not kill the
  run that would produce the next one).
* **Async by default.** The device→host snapshot happens on the caller's
  thread (the state may be donated by the very next step); file I/O,
  hashing, and the commit rename run on a per-directory writer thread.
  ``wait_for_saves`` joins the queue — the Trainer calls it on every exit
  path.

Why not Orbax here: on this image the tensorstore restore path corrupts
the process heap when the XLA persistent compilation cache is enabled
(the long-standing train→resume segfault in ``tests/test_e2e.py``), and
its OCDBT on-disk format is opaque to content verification. Arrays are
stored as raw little-endian buffers (``train_state/l<idx>.bin``) named in
``MANIFEST.json`` with their pytree path, shape, and dtype — every byte
on disk is hashable and attributable. Restore reads host-side and places
onto the *target* state's shardings, which preserves the cross-mesh-shape
resume capability the Orbax path had.

Checkpoint layout (``<dir>/<step>/``)::

    train_state/l00000.bin ...   raw array bytes (little-endian, C order)
    train_meta.json              sidecar: data cursor, rng schedule, seeds
    MANIFEST.json                {leaves: [{name, shape, dtype, file,
                                 size, sha256}], meta_files: {...}}
    COMMIT                       {"manifest_sha256": ...} — written last

Telemetry (names pinned in ``tests/test_bench_contract.py``):
save/restore duration histograms, corrupt-skipped + save-retry counters,
and a last-verified-step gauge.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import queue
import re
import shutil
import threading
import time
from typing import Any, List, Optional, Tuple

import jax
import numpy as np

from dlti_tpu.telemetry.registry import Counter, Gauge, Histogram
from dlti_tpu.utils import durable_io
from dlti_tpu.utils.logging import get_logger

_FORMAT_VERSION = 1
_MANIFEST = "MANIFEST.json"
_COMMIT = "COMMIT"
_SIDECAR = "train_meta.json"
_ARRAY_DIR = "train_state"
_TMP_PREFIX = ".tmp-"
_QUARANTINE_DIR = "_quarantine"

# Checkpoint I/O spans milliseconds (tiny test states) to minutes (7B
# trees on network filesystems).
CKPT_IO_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                   5.0, 10.0, 30.0, 60.0, 120.0, 300.0)

# Exposition-name contract (pinned in tests/test_bench_contract.py, like
# the gateway and prefetch metric sets).
CKPT_METRIC_NAMES = (
    "dlti_ckpt_save_seconds",
    "dlti_ckpt_restore_seconds",
    "dlti_ckpt_corrupt_skipped",
    "dlti_ckpt_save_retries",
    "dlti_ckpt_last_verified_step",
)

save_seconds = Histogram(
    CKPT_METRIC_NAMES[0], CKPT_IO_BUCKETS,
    help="checkpoint write+commit duration (writer thread)",
    stats_key="ckpt_save_seconds")
restore_seconds = Histogram(
    CKPT_METRIC_NAMES[1], CKPT_IO_BUCKETS,
    help="checkpoint read+place duration",
    stats_key="ckpt_restore_seconds")
corrupt_skipped = Counter(
    CKPT_METRIC_NAMES[2],
    help="checkpoints quarantined as incomplete or corrupt")
save_retries = Counter(
    CKPT_METRIC_NAMES[3],
    help="checkpoint save attempts retried after an I/O failure")
last_verified_step = Gauge(
    CKPT_METRIC_NAMES[4],
    help="newest checkpoint step that passed digest verification")


class CheckpointCorruptError(RuntimeError):
    """A checkpoint failed integrity verification (truncated / bit-flipped
    / missing commit marker). Resume paths quarantine and fall back."""


# ----------------------------------------------------------------------
# Leaf codec: jax/np array <-> raw bytes + (name, shape, dtype) metadata
# ----------------------------------------------------------------------

def _leaf_entries(state: Any) -> Tuple[List[dict], List[bytes]]:
    """Snapshot every array leaf to host bytes NOW (the caller may donate
    the device buffers to the next step immediately after)."""
    leaves_with_path, _ = jax.tree_util.tree_flatten_with_path(state)
    # Multi-host: consolidate every cross-process leaf to a full host
    # array in ONE jitted replicate launch (collective — every process
    # participates; rank 0 alone writes files). One launch, not one
    # process_allgather per leaf: on the gloo CPU backend, dozens of
    # tiny back-to-back cross-process launches intermittently wedge or
    # abort ("op.preamble.length <= op.nbytes") when one rank enters
    # launch n+1 while its peer still drains launch n's socket buffers —
    # a single launch gives XLA one rendezvous and per-op channel ids.
    # Consolidated checkpoints also make resume onto a different process
    # count trivial.
    cross = [i for i, (_, leaf) in enumerate(leaves_with_path)
             if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable]
    consolidated: dict = {}
    if cross:
        from jax.sharding import NamedSharding, PartitionSpec

        # Cross-process leaves are mesh-placed (NamedSharding): the same
        # mesh, replicated.
        ins = [leaves_with_path[i][1] for i in cross]
        reps = [NamedSharding(x.sharding.mesh, PartitionSpec()) for x in ins]
        outs = jax.jit(lambda xs: xs, out_shardings=reps)(ins)
        for i, out in zip(cross, outs):
            consolidated[i] = np.asarray(out.addressable_data(0))
    metas, payloads = [], []
    for i, (path, leaf) in enumerate(leaves_with_path):
        if i in consolidated:
            host = consolidated[i]
        else:
            host = np.asarray(jax.device_get(leaf))
        if not host.flags["C_CONTIGUOUS"]:
            # Note: ascontiguousarray promotes 0-d to 1-d, hence the guard
            # (0-d is always contiguous).
            host = np.ascontiguousarray(host)
        metas.append({
            "name": jax.tree_util.keystr(path),
            "shape": list(host.shape),
            "dtype": host.dtype.name,
            "file": f"{_ARRAY_DIR}/l{i:05d}.bin",
        })
        payloads.append(host.tobytes())
    return metas, payloads


def _decode_leaf(raw: bytes, meta: dict) -> np.ndarray:
    # np.dtype resolves ml_dtypes names (bfloat16, ...) once jax is
    # imported, which registers them.
    dtype = np.dtype(meta["dtype"])
    arr = np.frombuffer(raw, dtype=dtype)
    return arr.reshape(tuple(meta["shape"]))


def _sha256_bytes(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


def _sha256_file(path: str, chunk: int = 1 << 20) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while True:
            block = f.read(chunk)
            if not block:
                break
            h.update(block)
    return h.hexdigest()


def _fsync_write(path: str, data: bytes,
                 path_class: str = "checkpoint") -> None:
    # Durable-writer policy (dlti_tpu.utils.durable_io): transient errnos
    # retry with backoff, ENOSPC reclaims quarantine/dump/cold-tier space
    # then retries, persistent failure re-raises for the caller's
    # skip-and-alert / degrade fallback.
    durable_io.write_bytes(path, data, path_class=path_class, fsync=True)


def _fsync_dir(path: str) -> None:
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return  # some filesystems refuse O_RDONLY on dirs; best effort
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


# ----------------------------------------------------------------------
# Async writer: one thread + FIFO queue per checkpoint directory
# ----------------------------------------------------------------------

@dataclasses.dataclass
class _PendingSave:
    step: int
    leaf_metas: List[dict]
    payloads: List[bytes]
    train_meta: Optional[dict]
    keep: Optional[int]
    retries: int
    retry_backoff_s: float
    # Durable-writer criticality class: "checkpoint" for train state,
    # "adapter" / "prefix_tier" when save_pytree serves those callers.
    path_class: str = "checkpoint"


class _Writer:
    def __init__(self, directory: str):
        self.directory = directory
        self._q: "queue.Queue[Optional[_PendingSave]]" = queue.Queue()
        self._idle = threading.Event()
        self._idle.set()
        self.last_error: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._run, name="dlti-ckpt-writer", daemon=True)
        self._thread.start()

    def submit(self, pending: _PendingSave) -> None:
        self._idle.clear()
        self._q.put(pending)

    def wait(self) -> None:
        self._q.join()
        self._idle.wait()

    @property
    def busy(self) -> bool:
        return not self._idle.is_set()

    def _run(self) -> None:
        while True:
            pending = self._q.get()
            try:
                if pending is not None:
                    _write_and_commit(self.directory, pending)
            except BaseException as e:  # noqa: BLE001 — logged, not fatal
                self.last_error = e
                get_logger().error(
                    "checkpoint save at step %s FAILED after retries: %s",
                    getattr(pending, "step", "?"), e)
            finally:
                # Drop the payload before blocking in get(): an idle
                # writer would otherwise pin the last save's bytes (the
                # whole state, again) in host memory until the next one.
                pending = None
                self._q.task_done()
                if self._q.unfinished_tasks == 0:
                    self._idle.set()


_writers: dict = {}
_writers_lock = threading.Lock()


def _writer(directory: str) -> _Writer:
    directory = os.path.abspath(directory)
    with _writers_lock:
        w = _writers.get(directory)
        if w is None:
            w = _writers[directory] = _Writer(directory)
            # ENOSPC escape hatch: this directory's quarantined wreckage
            # is the first thing a reclaim pass quota-evicts.
            durable_io.register_reclaimer(
                f"ckpt-quarantine:{directory}",
                durable_io.quarantine_reclaimer(directory))
        return w


def _write_and_commit(directory: str, p: _PendingSave) -> None:
    """Full atomic-finalize protocol, with bounded retry/backoff."""
    t0 = time.perf_counter()
    final = os.path.join(directory, str(p.step))
    attempt = 0
    while True:
        tmp = os.path.join(
            directory, f"{_TMP_PREFIX}{p.step}-{os.getpid()}-{attempt}")
        try:
            if os.path.isdir(final):
                return  # idempotent: this step is already committed
            _write_staging(tmp, p)
            durable_io.replace(tmp, final, path_class=p.path_class)
            _fsync_dir(directory)
            break
        except Exception:
            shutil.rmtree(tmp, ignore_errors=True)
            attempt += 1
            if attempt > max(0, p.retries):
                raise
            save_retries.inc()
            time.sleep(p.retry_backoff_s * (2 ** (attempt - 1)))
    if p.keep:
        _rotate(directory, p.keep)
    last_verified_step.set(p.step)
    save_seconds.observe(time.perf_counter() - t0)


def _write_staging(tmp: str, p: _PendingSave) -> None:
    os.makedirs(os.path.join(tmp, _ARRAY_DIR), exist_ok=True)
    manifest: dict = {
        "format": _FORMAT_VERSION,
        "step": p.step,
        "leaves": [],
        "meta_files": {},
    }
    for meta, payload in zip(p.leaf_metas, p.payloads):
        _fsync_write(os.path.join(tmp, meta["file"]), payload,
                     p.path_class)
        entry = dict(meta)
        entry["size"] = len(payload)
        entry["sha256"] = _sha256_bytes(payload)
        manifest["leaves"].append(entry)
    if p.train_meta is not None:
        data = json.dumps(p.train_meta, indent=2, sort_keys=True).encode()
        _fsync_write(os.path.join(tmp, _SIDECAR), data, p.path_class)
        manifest["meta_files"][_SIDECAR] = {
            "size": len(data), "sha256": _sha256_bytes(data)}
    mbytes = json.dumps(manifest, indent=2, sort_keys=True).encode()
    _fsync_write(os.path.join(tmp, _MANIFEST), mbytes, p.path_class)
    # The commit marker is written LAST and names the manifest's digest:
    # a torn copy of this directory (e.g. a partial rsync, or a non-atomic
    # rename on an exotic filesystem) cannot present a valid COMMIT over a
    # mismatched manifest.
    _fsync_write(os.path.join(tmp, _COMMIT), json.dumps(
        {"manifest_sha256": _sha256_bytes(mbytes)}).encode(),
        p.path_class)
    _fsync_dir(os.path.join(tmp, _ARRAY_DIR))
    _fsync_dir(tmp)


def _rotate(directory: str, keep: int) -> None:
    steps = list_checkpoint_steps(directory)
    for step in steps[:-keep] if keep > 0 else []:
        shutil.rmtree(os.path.join(directory, str(step)),
                      ignore_errors=True)


# ----------------------------------------------------------------------
# Public API (same surface the Orbax backend exposed, plus verification)
# ----------------------------------------------------------------------

def save_train_state(directory: str, step: int, state: Any,
                     keep: Optional[int] = 3, async_save: bool = True,
                     train_meta: Optional[dict] = None,
                     retries: int = 3,
                     retry_backoff_s: float = 0.2) -> None:
    """Checkpoint ``state`` under ``directory/step`` atomically.

    The device→host snapshot is taken synchronously (the caller may donate
    the state to the next step right after this returns); writing,
    hashing, and the commit rename happen on the directory's writer thread
    when ``async_save`` (call :func:`wait_for_saves` to settle them).
    """
    directory = os.path.abspath(directory)
    os.makedirs(directory, exist_ok=True)
    leaf_metas, payloads = _leaf_entries(state)  # collective multi-host
    if jax.process_count() > 1 and jax.process_index() != 0:
        return  # rank 0 writes the consolidated checkpoint
    pending = _PendingSave(
        step=int(step), leaf_metas=leaf_metas, payloads=payloads,
        train_meta=train_meta, keep=keep, retries=retries,
        retry_backoff_s=retry_backoff_s)
    if async_save:
        _writer(directory).submit(pending)
    else:
        _write_and_commit(directory, pending)


def wait_for_saves(directory: str) -> None:
    """Block until every queued async save for ``directory`` has committed
    (or exhausted its retries — failures are logged, not raised, so exit
    paths can settle saves without masking the original exception)."""
    w = _writers.get(os.path.abspath(directory))
    if w is not None:
        w.wait()


def list_checkpoint_steps(directory: str) -> List[int]:
    """Committed (renamed-into-place) checkpoint steps, ascending. Staging
    (``.tmp-*``) and quarantined dirs are never listed."""
    if not os.path.isdir(directory):
        return []
    steps = []
    for name in os.listdir(directory):
        if name.isdigit() and os.path.isdir(os.path.join(directory, name)):
            steps.append(int(name))
    return sorted(steps)


def latest_step(directory: str) -> Optional[int]:
    """Newest committed step (no content verification — see
    :func:`latest_verified_step` for the resume-grade scan)."""
    steps = list_checkpoint_steps(directory)
    return steps[-1] if steps else None


def verify_checkpoint(directory: str, step: int) -> Tuple[bool, str]:
    """Deep integrity check: commit marker present, manifest digest
    matches the marker, and every listed file exists with the recorded
    size and SHA-256. Returns (ok, reason)."""
    return _verify_root(os.path.join(os.path.abspath(directory), str(step)))


def verify_pytree_dir(directory: str) -> Tuple[bool, str]:
    """The same deep integrity check for a :func:`save_pytree` artifact
    (an export dir rather than a numbered step dir) — the re-verify the
    rolling-reload path runs immediately before each per-replica swap, so
    an export corrupted mid-roll aborts the roll instead of canary-failing
    halfway through it. Returns (ok, reason)."""
    return _verify_root(os.path.abspath(directory))


def manifest_digest(directory: str) -> Optional[str]:
    """The committed manifest's SHA-256 for a checkpoint step dir or a
    :func:`save_pytree` export dir — the identity deploy/promote paths pin
    ("which bytes is the fleet serving"). None when the dir has no commit
    marker or it is unreadable."""
    try:
        with open(os.path.join(os.path.abspath(directory), _COMMIT),
                  "rb") as f:
            return json.loads(f.read()).get("manifest_sha256")
    except (OSError, ValueError):
        return None


def _verify_root(root: str) -> Tuple[bool, str]:
    commit_path = os.path.join(root, _COMMIT)
    manifest_path = os.path.join(root, _MANIFEST)
    if not os.path.isfile(commit_path):
        return False, "missing-commit"
    if not os.path.isfile(manifest_path):
        return False, "missing-manifest"
    try:
        with open(manifest_path, "rb") as f:
            mbytes = f.read()
        commit = json.loads(open(commit_path, "rb").read())
        if commit.get("manifest_sha256") != _sha256_bytes(mbytes):
            return False, "manifest-digest-mismatch"
        manifest = json.loads(mbytes)
    except (ValueError, OSError):
        return False, "bad-manifest"
    entries = list(manifest.get("leaves", []))
    entries += [dict(v, file=k)
                for k, v in manifest.get("meta_files", {}).items()]
    for entry in entries:
        path = os.path.join(root, entry["file"])
        if not os.path.isfile(path):
            return False, f"missing-file:{entry['file']}"
        if os.path.getsize(path) != entry["size"]:
            return False, f"size-mismatch:{entry['file']}"
        if _sha256_file(path) != entry["sha256"]:
            return False, f"digest-mismatch:{entry['file']}"
    return True, "ok"


def quarantine_step(directory: str, name: str, reason: str) -> Optional[str]:
    """Move a checkpoint (or staging dir) aside instead of deleting it —
    the bytes stay available for forensics; the resume scan stops seeing
    it. Returns the quarantine path."""
    directory = os.path.abspath(directory)
    src = os.path.join(directory, name)
    if not os.path.exists(src):
        return None
    qdir = os.path.join(directory, _QUARANTINE_DIR)
    os.makedirs(qdir, exist_ok=True)
    safe_reason = "".join(c if c.isalnum() or c in "-_." else "-"
                          for c in reason)
    k = 0
    while True:
        dst = os.path.join(qdir, f"{name.lstrip('.')}__{safe_reason}__{k}")
        if not os.path.exists(dst):
            break
        k += 1
    durable_io.replace(src, dst, path_class="checkpoint")
    # Quarantined wreckage is reclaimable the moment it exists (the
    # async-writer path registers this too; save_pytree-only directories
    # — adapters, tier blocks — get their hatch here).
    durable_io.register_reclaimer(
        f"ckpt-quarantine:{directory}",
        durable_io.quarantine_reclaimer(directory))
    corrupt_skipped.inc()
    get_logger().warning(
        "quarantined checkpoint %s (%s) -> %s", src, reason, dst)
    return dst


def latest_verified_step(directory: str) -> Optional[int]:
    """Newest step that passes :func:`verify_checkpoint`. Anything newer
    that fails is quarantined (renamed, counted, logged) so the next scan
    does not re-pay its verification cost. Stale staging dirs from killed
    saves are quarantined too."""
    directory = os.path.abspath(directory)
    if not os.path.isdir(directory):
        return None
    w = _writers.get(directory)
    if w is None or not w.busy:
        # A kill mid-async-save leaves a .tmp-* staging dir; with no
        # writer active it can only be stale.
        for name in sorted(os.listdir(directory)):
            if name.startswith(_TMP_PREFIX):
                quarantine_step(directory, name, "incomplete-save")
    for step in reversed(list_checkpoint_steps(directory)):
        ok, reason = verify_checkpoint(directory, step)
        if ok:
            last_verified_step.set(step)
            return step
        quarantine_step(directory, str(step), reason)
    return None


def load_train_meta(directory: str, step: int) -> Optional[dict]:
    """The sidecar written alongside the arrays (data-pipeline cursor, rng
    schedule, seeds). None for checkpoints saved without one."""
    path = os.path.join(os.path.abspath(directory), str(step), _SIDECAR)
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        return json.load(f)


def restore_train_state(directory: str, step: int, target: Any) -> Any:
    """Restore into the structure/shardings of ``target``.

    ``target`` is a live (possibly sharded) state template — typically a
    freshly initialized one; arrays are read host-side and placed with the
    template's shardings, so a run can resume onto a different mesh shape
    than it saved from. Raises :class:`CheckpointCorruptError` on
    unreadable/corrupt data and ``ValueError`` on a genuine structure
    mismatch (different model/optimizer config)."""
    t0 = time.perf_counter()
    root = os.path.join(os.path.abspath(directory), str(step))
    manifest_path = os.path.join(root, _MANIFEST)
    try:
        with open(manifest_path) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as e:
        raise CheckpointCorruptError(
            f"unreadable manifest for step {step} under {directory}: {e}"
        ) from e
    leaves_with_path, treedef = jax.tree_util.tree_flatten_with_path(target)
    entries = manifest.get("leaves", [])
    if len(entries) != len(leaves_with_path):
        raise ValueError(
            f"checkpoint step {step} has {len(entries)} array leaves but "
            f"the target state has {len(leaves_with_path)} — the run "
            "config (model/optimizer/LoRA/fp16) does not match the "
            "checkpoint")
    placed = []
    for entry, (path, leaf) in zip(entries, leaves_with_path):
        name = jax.tree_util.keystr(path)
        if entry["name"] != name:
            raise ValueError(
                f"checkpoint leaf {entry['name']!r} does not line up with "
                f"target leaf {name!r} (structure mismatch)")
        want_shape = tuple(entry["shape"])
        want_dtype = entry["dtype"]
        t_shape = tuple(getattr(leaf, "shape", ()))
        t_dtype = getattr(getattr(leaf, "dtype", None), "name", None)
        if t_shape != want_shape or (t_dtype and t_dtype != want_dtype):
            raise ValueError(
                f"checkpoint leaf {name} is {want_dtype}{list(want_shape)} "
                f"but the target expects {t_dtype}{list(t_shape)}")
        fpath = os.path.join(root, entry["file"])
        try:
            with open(fpath, "rb") as f:
                raw = f.read()
        except OSError as e:
            raise CheckpointCorruptError(
                f"unreadable array file {entry['file']} for step {step}: "
                f"{e}") from e
        if len(raw) != entry["size"]:
            raise CheckpointCorruptError(
                f"array file {entry['file']} is {len(raw)} bytes, manifest "
                f"says {entry['size']} (truncated?)")
        host = _decode_leaf(raw, entry)
        placed.append(_place_like(host, leaf))
    restored = _launder(jax.tree_util.tree_unflatten(treedef, placed))
    restore_seconds.observe(time.perf_counter() - t0)
    return restored


def _place_like(host: np.ndarray, template: Any):
    """Put a host array onto the template leaf's sharding (cross-mesh
    resume: the restored value adopts the *current* run's layout)."""
    sharding = getattr(template, "sharding", None)
    if sharding is None:
        return jax.device_put(host)
    if jax.process_count() > 1:
        # Multi-host: each process materializes only its addressable
        # shards from the (shared-filesystem) full array.
        return jax.make_array_from_callback(
            host.shape, sharding, lambda idx: host[idx])
    return jax.device_put(host, sharding)


def save_pytree(directory: str, tree: Any, *,
                path_class: str = "checkpoint") -> str:
    """Write an arbitrary pytree (e.g. an export's params dict) with the
    same manifest+commit protocol as a step checkpoint, synchronously and
    atomically (staging dir + rename). Returns ``directory``.

    ``path_class`` selects the durable-writer criticality (``"adapter"``
    for LoRA exports, ``"prefix_tier"`` for KV-block demotions). A save
    that fails mid-staging quarantines its partial staging dir (never a
    stray ``.tmp-*``, never a torn committed dir) and re-raises."""
    directory = os.path.abspath(directory)
    parent = os.path.dirname(directory) or "."
    os.makedirs(parent, exist_ok=True)
    leaf_metas, payloads = _leaf_entries(tree)
    pending = _PendingSave(
        step=0, leaf_metas=leaf_metas, payloads=payloads, train_meta=None,
        keep=None, retries=3, retry_backoff_s=0.2, path_class=path_class)
    tmp = f"{directory}{_TMP_PREFIX}{os.getpid()}"
    shutil.rmtree(tmp, ignore_errors=True)
    try:
        _write_staging(tmp, pending)
        if os.path.isdir(directory):
            shutil.rmtree(directory)
        durable_io.replace(tmp, directory, path_class=path_class)
    except BaseException:
        # Torn/failed staging: quarantine the partial bytes for forensics
        # (falling back to plain removal when even the rename is sick).
        try:
            quarantine_step(parent, os.path.basename(tmp), "save-failed")
        except OSError:
            shutil.rmtree(tmp, ignore_errors=True)
        raise
    _fsync_dir(parent)
    return directory


_KEY_RE = re.compile(r"\['([^']*)'\]")


def load_pytree(directory: str, verify: bool = False) -> Any:
    """Load a :func:`save_pytree` artifact back into nested dicts (leaf
    names are parsed from the manifest's pytree paths — dict-keyed trees
    only, which covers params exports)."""
    directory = os.path.abspath(directory)
    manifest_path = os.path.join(directory, _MANIFEST)
    try:
        with open(manifest_path) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as e:
        raise CheckpointCorruptError(
            f"unreadable manifest under {directory}: {e}") from e
    out: dict = {}
    for entry in manifest.get("leaves", []):
        keys = _KEY_RE.findall(entry["name"])
        if not keys or "".join(f"['{k}']" for k in keys) != entry["name"]:
            raise ValueError(
                f"leaf {entry['name']!r} is not a dict-keyed path; "
                "load_pytree only handles nested-dict trees")
        path = os.path.join(directory, entry["file"])
        with open(path, "rb") as f:
            raw = f.read()
        if len(raw) != entry["size"] or (
                verify and _sha256_bytes(raw) != entry["sha256"]):
            raise CheckpointCorruptError(
                f"array file {entry['file']} under {directory} failed "
                "integrity check")
        node = out
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = _decode_leaf(raw, entry)
    return out


def _launderable(x: Any) -> bool:
    if not hasattr(x, "dtype") or not hasattr(x, "sharding"):
        return False
    # Host-pinned leaves (optimizer offload) stay as transfer products: an
    # elementwise op on pinned_host operands may not lower. Everything
    # else launders — note the CPU backend names its *default* memory
    # space "unpinned_host", so the test must be pinned-host-only, not
    # device-only.
    return getattr(x.sharding, "memory_kind", None) != "pinned_host"


def _launder(tree: Any) -> Any:
    """Pass restored arrays through a jitted elementwise copy.

    On this image's CPU jaxlib, *donating* a transfer-created array (a
    ``jax.device_put`` of host numpy — which may alias the Python-owned
    buffer zero-copy) into the compiled train step corrupts the process
    heap: the historical train→resume segfault in ``tests/test_e2e.py``,
    reproduced with transfer-created arrays alone, no checkpoint I/O
    involved. Executable *outputs* are immune (the runs that crashed on a
    restored state always continued fine from a live one). The training
    step donates its state, so restored states must be executable
    outputs, not transfer products. The copy is NOT donated — donation is
    the hazard being laundered away — costing one transient extra
    state-size allocation during restore.
    """
    import jax.numpy as jnp

    flags = [_launderable(x) for x in jax.tree_util.tree_leaves(tree)]
    if not any(flags):
        return tree

    def copy_tree(t):
        def copy_leaf(x):
            if not hasattr(x, "dtype"):
                return x
            if jnp.issubdtype(x.dtype, jnp.bool_):
                return jnp.logical_and(x, True)
            # +0 (not identity): jit(lambda x: x) returns the input
            # array object untouched, which would defeat the laundering.
            return x + jnp.zeros((), x.dtype)
        return jax.tree_util.tree_map(copy_leaf, t)

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    to_copy = [x for x, f in zip(leaves, flags) if f]
    copied = iter(jax.jit(copy_tree)(to_copy))
    out = [next(copied) if f else x for x, f in zip(leaves, flags)]
    return jax.tree_util.tree_unflatten(treedef, out)


def restore_latest_verified(directory: str, target: Any,
                            ) -> Optional[Tuple[Any, int, Optional[dict]]]:
    """Resume entry point: restore the newest checkpoint that verifies,
    quarantining and falling back past any that turn out corrupt even
    after passing the scan (TOCTOU / read errors). Returns
    ``(state, step, sidecar_meta)`` or None when nothing restorable
    exists. ``ValueError`` (structure mismatch) propagates — that is a
    config error, not corruption."""
    while True:
        step = latest_verified_step(directory)
        if step is None:
            return None
        try:
            state = restore_train_state(directory, step, target)
            return state, step, load_train_meta(directory, step)
        except CheckpointCorruptError as e:
            get_logger().warning(
                "verified checkpoint step %d failed on restore (%s); "
                "falling back", step, e)
            quarantine_step(directory, str(step), "restore-failed")
