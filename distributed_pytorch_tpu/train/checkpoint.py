"""Checkpoint / resume via orbax — sharded-pytree save and restore.

This strictly exceeds the reference, whose only persistence is an
end-of-run `torch.save` in the single-GPU trainer (single-gpu/train.py:
361-372) while the DDP and FSDP save blocks are dead-coded with `and False`
(multi-gpu/ddp/train.py:339, kaggle-fsdp.py:1141) and no resume path exists
anywhere (SURVEY.md §5 checkpoint/resume). Here:

* saves are *sharded*: each host writes only its addressable shards (the
  TPU-native equivalent of the FSDP FULL_STATE_DICT rank-0 gather the
  reference demonstrates but disables, kaggle-fsdp.py:1143-1148 — without
  the gather's O(model) host memory spike);
* restore takes the target shardings, so a checkpoint written on one mesh
  can be read onto another (recipe migration: train fsdp, serve tp);
* mid-training interval saves + resume (`TrainConfig.ckpt_interval`,
  `resume`), which the reference names as future work (ddp/train.py:340);
* saves are *verified* (ISSUE 13): every durable step dir carries a
  blake2b per-file manifest; `restore_checkpoint` verifies it before
  handing bytes to the trainer, `latest_step_dir` skips torn/partial
  dirs, and `restore_latest` falls back to the previous good step dir on
  corruption instead of crashing — the contract the elastic supervisor
  (train/supervisor.py) restarts against.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import shutil
from typing import Any, Optional

import jax
import orbax.checkpoint as ocp

from distributed_pytorch_tpu import config as cfg_mod
from distributed_pytorch_tpu.config import LLMConfig, TrainConfig
from distributed_pytorch_tpu.train.state import TrainState


def _abs(path: str) -> str:
    return os.path.abspath(os.path.expanduser(path))


def _write_meta(path: str, state: TrainState, model_cfg, train_cfg) -> None:
    if model_cfg is None:
        return
    meta = {
        "model_config": dataclasses.asdict(model_cfg),
        "train_config": dataclasses.asdict(train_cfg) if train_cfg else {},
        "step": int(jax.device_get(state.step)),
    }
    if jax.process_index() == 0:
        # an async save may not have made the step's directory yet (orbax
        # creates it on a background thread; under load the write lost)
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, "config.json"), "w") as f:
            json.dump(meta, f, indent=2)


# ---------------------------------------------------------------------------
# Verified checkpoints (ISSUE 13): a durable step dir carries manifest.json —
# one blake2b digest + byte count per file under the dir. The manifest is
# written ONLY after the orbax save is durable (immediately for blocking
# saves; at the next wait for async ones), so its presence doubles as the
# durability marker: a crash between an async save's dispatch and its
# background commit leaves a manifest-less dir that latest_step_dir skips.
# ---------------------------------------------------------------------------

MANIFEST = "manifest.json"
_HASH_CHUNK = 1 << 20


class CheckpointCorrupt(RuntimeError):
    """A step dir failed manifest verification (flipped bytes, truncated
    or missing files, torn save). Carries the violation list."""

    def __init__(self, path: str, violations: list[str]):
        super().__init__(f"checkpoint {path} failed verification: "
                         + "; ".join(violations[:4])
                         + (" …" if len(violations) > 4 else ""))
        self.path = path
        self.violations = violations


def _ckpt_files(path: str) -> list[str]:
    """Relative paths of every payload file under a step dir (the
    manifest itself and tmp leftovers excluded)."""
    out = []
    for dirpath, _, files in os.walk(path):
        for name in sorted(files):
            rel = os.path.relpath(os.path.join(dirpath, name), path)
            if rel == MANIFEST or name.endswith(".tmp"):
                continue
            out.append(rel)
    return sorted(out)


def _blake2b_file(path: str) -> str:
    h = hashlib.blake2b(digest_size=16)
    with open(path, "rb") as f:
        while True:
            chunk = f.read(_HASH_CHUNK)
            if not chunk:
                break
            h.update(chunk)
    return h.hexdigest()


def write_manifest(path: str) -> Optional[str]:
    """Write `manifest.json` for a durable step dir (process 0 only on a
    pod — every host sees the shared fs). Atomic tmp+rename so a reader
    never sees a torn manifest. Returns the manifest path (None on
    non-zero processes)."""
    if jax.process_index() != 0:
        return None
    path = _abs(path)
    files = {rel: {"blake2b": _blake2b_file(os.path.join(path, rel)),
                   "bytes": os.path.getsize(os.path.join(path, rel))}
             for rel in _ckpt_files(path)}
    mpath = os.path.join(path, MANIFEST)
    tmp = mpath + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"version": 1, "files": files}, f, indent=1)
    os.replace(tmp, mpath)
    return mpath


def verify_manifest(path: str, *, deep: bool = True) -> list[str]:
    """Check a step dir against its manifest; returns the violation list
    ([] = good). `deep=False` checks existence + byte counts only (the
    cheap screen latest_step_dir runs per candidate); `deep=True` also
    re-hashes every file — a single flipped byte is caught."""
    path = _abs(path)
    mpath = os.path.join(path, MANIFEST)
    if not os.path.exists(mpath):
        return [f"{MANIFEST} missing (torn or pre-manifest save)"]
    try:
        with open(mpath) as f:
            manifest = json.load(f)
        files = manifest["files"]
    except (ValueError, KeyError) as e:
        return [f"{MANIFEST} unreadable: {e!r}"]
    violations = []
    for rel, meta in files.items():
        fp = os.path.join(path, rel)
        if not os.path.exists(fp):
            violations.append(f"{rel}: missing")
            continue
        size = os.path.getsize(fp)
        if size != meta["bytes"]:
            violations.append(f"{rel}: {size} bytes, manifest says "
                              f"{meta['bytes']} (truncated/torn)")
            continue
        if deep and _blake2b_file(fp) != meta["blake2b"]:
            violations.append(f"{rel}: blake2b mismatch (corrupt)")
    return violations


def weights_version(path: str) -> Optional[str]:
    """Identity string for the weights under a step dir:
    `<step_dir_basename>-<blake2b(manifest)[:8]>`. The manifest already
    digests every payload file, so hashing the manifest bytes gives a
    version that changes iff any weight byte changed — cheap enough to
    compute at load time. None when the dir has no manifest (demo /
    pre-manifest checkpoints)."""
    path = _abs(path)
    try:
        with open(os.path.join(path, MANIFEST), "rb") as f:
            digest = hashlib.blake2b(f.read(), digest_size=16).hexdigest()
    except OSError:
        return None
    return f"{os.path.basename(os.path.normpath(path))}-{digest[:8]}"


def save_checkpoint(path: str, state: TrainState,
                    model_cfg: Optional[LLMConfig] = None,
                    train_cfg: Optional[TrainConfig] = None) -> str:
    """Write `state` (sharded) + configs (json) under `path`. Blocks until
    the save is durable — use for final/preemption saves. The manifest is
    written immediately (the save already committed)."""
    path = _abs(path)
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(os.path.join(path, "state"), state, force=True)
    _write_meta(path, state, model_cfg, train_cfg)
    write_manifest(path)
    return path


_async_ckptr: Optional[ocp.AsyncCheckpointer] = None

# Double-buffered snapshot state: the PREVIOUS interval save's snapshot
# pytree. After wait_until_finished its buffers are idle, so on TPU they
# are DONATED as the destination of the next snapshot's per-leaf copies —
# steady-state interval saves allocate nothing and the copy cost is pure
# HBM bandwidth. (CPU jit ignores donation with a warning on this jax, so
# there the per-leaf copies simply allocate; same semantics.)
_snapshot_prev: Optional[TrainState] = None
#: wall-clock ms of the most recent pre-save snapshot copy — the
#: `ckpt_snapshot_ms` metric the training loop logs so the 1.5B
#: step-time dent is visible (ROADMAP async-checkpoint item).
last_snapshot_ms: float = 0.0

_copy_into = None  # lazily-built jitted per-leaf donated copy


def _leaf_copy_fns():
    global _copy_into
    if _copy_into is None:
        import functools
        import jax.numpy as jnp
        # dst is donated and otherwise unused: jax pairs donated inputs
        # with same-shaped outputs, so the copy of src lands in dst's
        # buffer. `+ 0`-style identity would alias src instead; lax.copy
        # semantics via jnp.copy inside jit forces a materialized value.
        _copy_into = jax.jit(
            lambda dst, src: jnp.copy(src), donate_argnums=(0,))
    return _copy_into


def _snapshot_state(state: TrainState) -> TrainState:
    """Donation-proof pre-save snapshot with per-leaf buffer reuse.

    The train step donates its state argument (train/step.py
    donate_argnums=(0,)), so the buffers behind `state` are REUSED by the
    very next optimizer step while orbax's background thread is still
    reading them — observed live on the CPU mesh: an interval save at
    it=4 persisted state.step == 7 (the run's final state), which made
    --resume skip the remaining iterations entirely. The snapshot copy is
    that race's fix, paid explicitly; this version reuses the previous
    (now idle) snapshot's buffers per leaf instead of allocating a fresh
    full-state copy each save, and records the measured copy time in
    `last_snapshot_ms`."""
    global _snapshot_prev, last_snapshot_ms
    import time

    t0 = time.perf_counter()
    prev = _snapshot_prev
    reuse = False
    if prev is not None and jax.default_backend() == "tpu":
        try:
            pl = jax.tree_util.tree_leaves(prev)
            sl = jax.tree_util.tree_leaves(state)
            reuse = (jax.tree_util.tree_structure(prev)
                     == jax.tree_util.tree_structure(state)
                     and len(pl) == len(sl)
                     and all(isinstance(a, jax.Array)
                             and isinstance(b, jax.Array)
                             and a.shape == b.shape and a.dtype == b.dtype
                             and a.sharding == b.sharding
                             for a, b in zip(pl, sl)))
        except Exception:  # noqa: BLE001 — reuse is a pure optimization
            reuse = False
    if reuse:
        copy = _leaf_copy_fns()
        snap = jax.tree_util.tree_map(
            lambda dst, src: copy(dst, src)
            if isinstance(src, jax.Array) else src, prev, state)
    else:
        snap = jax.tree_util.tree_map(
            lambda x: x.copy() if isinstance(x, jax.Array) else x, state)
    snap = jax.block_until_ready(snap)  # measure the copy, not dispatch
    last_snapshot_ms = (time.perf_counter() - t0) * 1e3
    _snapshot_prev = snap
    return snap


def save_checkpoint_async(path: str, state: TrainState,
                          model_cfg: Optional[LLMConfig] = None,
                          train_cfg: Optional[TrainConfig] = None) -> str:
    """Non-blocking interval save: device buffers are snapshotted (per-leaf
    copies into the previous snapshot's reused buffers — `_snapshot_state`;
    copy time in `last_snapshot_ms`), the serialization runs on background
    threads, and training continues — the reference's (dead-coded) saves
    all block (kaggle-fsdp.py:1141). Any in-flight previous save is waited
    on first (bounds host memory to one outstanding snapshot); call
    `wait_for_saves()` before process exit. Orbax finalizes atomically, so
    `latest_step_dir` never sees a torn checkpoint."""
    global _async_ckptr
    if _async_ckptr is None:
        _async_ckptr = ocp.AsyncCheckpointer(ocp.StandardCheckpointHandler())
    _async_ckptr.wait_until_finished()
    _flush_pending_manifests()  # previous async save is durable now
    path = _abs(path)
    state = _snapshot_state(state)
    _async_ckptr.save(os.path.join(path, "state"),
                      args=ocp.args.StandardSave(state), force=True)
    _write_meta(path, state, model_cfg, train_cfg)
    # manifest deferred: the bytes aren't durable until the background
    # commit — written at the next wait (here or wait_for_saves)
    _pending_manifests.append(path)
    return path


#: step dirs whose async save is dispatched but not yet known durable —
#: their manifests are written only after the next wait_until_finished.
_pending_manifests: list[str] = []


def _flush_pending_manifests() -> None:
    while _pending_manifests:
        p = _pending_manifests.pop(0)
        if os.path.isdir(p):
            write_manifest(p)


def wait_for_saves() -> None:
    """Block until all async interval saves are durable (and stamp their
    manifests — a dir only counts as a verified checkpoint after this)."""
    if _async_ckptr is not None:
        _async_ckptr.wait_until_finished()
    _flush_pending_manifests()


def load_configs(path: str) -> tuple[LLMConfig, TrainConfig, int]:
    with open(os.path.join(_abs(path), "config.json")) as f:
        meta = json.load(f)
    return (LLMConfig(**meta["model_config"]),
            TrainConfig(**meta["train_config"]),
            meta.get("step", 0))


def restore_checkpoint(path: str, abstract_state: Any,
                       state_sharding: Any = None) -> TrainState:
    """Restore into the given structure/shardings.

    `abstract_state`: a TrainState of ShapeDtypeStructs (jax.eval_shape of
    the init fn); with `state_sharding`, arrays come back already placed in
    their mesh shards. Without one (single-process inference, e.g. the
    sampling CLI), everything lands on the default device.

    When the step dir carries a manifest it is deep-verified first
    (CKPT_VERIFY knob, default on): a flipped byte raises
    `CheckpointCorrupt` BEFORE orbax hands poisoned bytes to the trainer.
    Pre-manifest (legacy) dirs restore unverified."""
    mpath = os.path.join(_abs(path), MANIFEST)
    if os.path.exists(mpath) and cfg_mod.knob("CKPT_VERIFY"):
        violations = verify_manifest(path, deep=True)
        if violations:
            raise CheckpointCorrupt(path, violations)
    if state_sharding is None:
        one = jax.sharding.SingleDeviceSharding(jax.local_devices()[0])
        state_sharding = jax.tree_util.tree_map(lambda s: one, abstract_state)
    abstract_state = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        abstract_state, state_sharding)
    with ocp.StandardCheckpointer() as ckptr:
        state = ckptr.restore(os.path.join(_abs(path), "state"),
                              abstract_state)
    # The restored arrays go straight into the (donating) train step. An
    # older jax needed a defensive `.copy()` of every leaf here — donating
    # orbax-restored buffers corrupted the heap; on jax 0.9.0 / orbax
    # 0.11.32 the resume, elastic and offload suites pass without it, and
    # the copy cost a transient second params+moments in device memory.
    return state


def restore_for_inference(path: str, abstract_state: Any,
                          shardings: Any = None) -> TrainState:
    """Restore ONLY params + moe_state (opt_state leaves are skipped via
    orbax PLACEHOLDER, which StandardCheckpointer rejects but the PyTree
    handler honors): the sampling CLI reads a third of the bytes a full
    TrainState restore would.

    `shardings`: optional pytree (matching abstract_state) of Shardings —
    pass the recipe tables' NamedShardings to restore a model larger than
    one device's memory directly into its mesh shards (sample.py --shard;
    round-3 weak #7). Default: everything on one local device."""
    if shardings is None:
        one = jax.sharding.SingleDeviceSharding(jax.local_devices()[0])
        shardings = jax.tree_util.tree_map(lambda s: one, abstract_state)
    placeholder = getattr(ocp, "PLACEHOLDER", None)
    if placeholder is None:
        # older orbax (no partial-restore placeholder): restore the full
        # state and drop opt_state after the fact — same result, reads the
        # extra bytes the placeholder path exists to skip
        state = restore_checkpoint(path, abstract_state, shardings)
        return dataclasses.replace(state, opt_state=None)
    abstract_state = dataclasses.replace(
        jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh),
            abstract_state, shardings),
        opt_state=jax.tree_util.tree_map(lambda _: placeholder,
                                         abstract_state.opt_state))
    restore_args = jax.tree_util.tree_map(
        lambda s: s if s is placeholder else
        ocp.checkpoint_utils.construct_restore_args(s),
        abstract_state)
    with ocp.Checkpointer(ocp.PyTreeCheckpointHandler()) as ckptr:
        state = ckptr.restore(
            os.path.join(_abs(path), "state"),
            args=ocp.args.PyTreeRestore(item=abstract_state,
                                        restore_args=restore_args))
    return dataclasses.replace(state, opt_state=None)


def _step_dirs(root: str) -> list[tuple[int, str]]:
    """(step, path) for every `step_*` dir under root, ascending."""
    root = _abs(root)
    if not os.path.isdir(root):
        return []
    out = []
    for name in os.listdir(root):
        if name.startswith("step_") and name[5:].isdigit():
            out.append((int(name[5:]), os.path.join(root, name)))
    return sorted(out)


def _complete_step_dir(path: str) -> bool:
    """Cheap completeness screen for one step dir: the orbax `state/`
    subdir finalized (it appears atomically, so presence+non-empty means
    the commit happened) and, when a manifest exists, every listed file
    is present at its recorded size. Dirs awaiting their manifest (async
    save dispatched, wait not reached) or pre-manifest legacy dirs pass
    the structural check only — byte-level trust is restore's deep
    verify."""
    sdir = os.path.join(path, "state")
    if not os.path.isdir(sdir) or not os.listdir(sdir):
        return False
    if os.path.exists(os.path.join(path, MANIFEST)):
        return not verify_manifest(path, deep=False)
    return True


def latest_step_dir(root: str) -> Optional[str]:
    """Find the newest COMPLETE `step_*` checkpoint dir under root.

    A torn or partial dir — orbax `state/` never finalized (crash between
    an async save's dispatch and its background commit; config.json is
    written eagerly), or files missing/truncated versus the manifest — is
    skipped, so --resume falls back to the previous durable checkpoint
    instead of crashing on it."""
    for _, path in reversed(_step_dirs(root)):
        if _complete_step_dir(path):
            return path
    return None


def restore_latest(root: str, abstract_state: Any,
                   state_sharding: Any = None,
                   ) -> Optional[tuple[TrainState, str, list[str]]]:
    """Restore the newest GOOD checkpoint under root, walking backwards
    past corrupt ones — the no-operator-intervention contract the elastic
    supervisor (train/supervisor.py) restarts against.

    Candidates newest→oldest; each is screened by `_complete_step_dir`,
    then deep-verified + restored by `restore_checkpoint`. A candidate
    failing either (flipped byte, torn file, orbax error) is recorded and
    the walk continues to the previous step dir. Returns
    `(state, path, skipped)` — `skipped` lists the rejected dirs — or
    None when no restorable checkpoint exists."""
    skipped: list[str] = []
    for _, path in reversed(_step_dirs(root)):
        if not _complete_step_dir(path):
            skipped.append(path)
            continue
        try:
            state = restore_checkpoint(path, abstract_state, state_sharding)
            return state, path, skipped
        except Exception as e:  # noqa: BLE001 — any
            # failed candidate must not kill the fallback walk; the next
            # older dir may be fine (that is the whole point)
            skipped.append(f"{path} ({type(e).__name__})")
    return None


def prune_checkpoints(root: str, keep: int) -> list[str]:
    """Retention (`--keep_ckpts K` / TRAIN_KEEP_CKPTS): delete the oldest
    VERIFIED step dirs so at most `keep` remain; returns deleted paths.

    Only manifest-carrying dirs that pass the shallow check count toward
    (or are eligible for) pruning: in-flight async dirs (manifest pending)
    and legacy/incomplete dirs are never touched, and the newest good dir
    always survives. keep <= 0 disables retention."""
    if keep <= 0 or jax.process_index() != 0:
        return []
    good = [p for _, p in _step_dirs(root)
            if os.path.exists(os.path.join(p, MANIFEST))
            and _complete_step_dir(p)]
    deleted = []
    for path in good[:-keep] if len(good) > keep else []:
        shutil.rmtree(path, ignore_errors=True)
        deleted.append(path)
    return deleted
