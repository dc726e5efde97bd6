"""The training loop: the runtime that replaces all five reference trainer
scripts (single-gpu/train.py:312-359, multi-gpu/ddp/train.py:291-337, and
the three kaggle variants' `:1068-1139` loops).

Per optimizer step: ONE jitted call executes the whole micro-batch
grad-accumulation scan, clip, and AdamW update (the reference runs a Python
micro-step loop with autocast/scaler bookkeeping); the host meanwhile
prefetches the next batch from the memmap (reference train.py:343 prefetch).
Logging: loss, dt, tokens/sec/chip and MFU (BASELINE.json metrics; the
reference logs only ms/step + reserved GB, train.py:354-359).

Observability (ISSUE 10, train/telemetry.py): per logged step the loop
feeds a flight-recorder ring ({it, loss, grad_norm, step_ms, data_ms,
sync_ms, ckpt_ms, tokens_per_s, mfu} -> runs/<run>/train_timeline.jsonl),
optionally serves it live over HTTP (`--metrics_port`), samples the
per-device HBM watermark against the memplan prediction, and drains the
loss/grad anomaly monitor — all at the existing sync boundaries, so the
per-step hot path stays device-async ('skip' anomaly handling itself is
compiled into the step, train/step.py). stats.json is written atomically
and refreshed at every checkpoint boundary.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import signal
import threading
import time
from typing import Any, Callable, Optional

import jax
import numpy as np

from distributed_pytorch_tpu import config as cfg_mod
from distributed_pytorch_tpu.config import LLMConfig, TrainConfig
from distributed_pytorch_tpu.data.loader import DataLoader, make_synthetic_bin
from distributed_pytorch_tpu.models.gpt import count_params
from distributed_pytorch_tpu.obs import paths as obs_paths
from distributed_pytorch_tpu.obs.trace import phase
from distributed_pytorch_tpu.parallel import sharding as shd
from distributed_pytorch_tpu.parallel.mesh import mesh_for
from distributed_pytorch_tpu.train import checkpoint as ckpt
from distributed_pytorch_tpu.train import memplan
from distributed_pytorch_tpu.train import metrics as M
from distributed_pytorch_tpu.train import telemetry
from distributed_pytorch_tpu.train.state import create_train_state
from distributed_pytorch_tpu.train.step import make_eval_step, make_train_step


def multihost_env_detected(environ=None) -> bool:
    """True when the environment announces a multi-process topology.

    Three announcement styles (round-3 VERDICT #2 — the old
    JAX_COORDINATOR_ADDRESS-only gate meant plain Cloud-TPU-pod bring-up
    silently ran each host disconnected):

    * explicit JAX env (JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES) set by
      our launchers (scripts/train_pod.sh) or the user;
    * Cloud TPU pod metadata: the TPU runtime exports TPU_WORKER_HOSTNAMES
      (comma-separated; >1 entry means a pod slice spanning hosts);
    * multislice (megascale) coordinator: MEGASCALE_COORDINATOR_ADDRESS.
    """
    if environ is None:
        # Route through the knob registry (config.ENV_KNOBS) so the
        # topology variables show up in `--knobs`; tests still inject a
        # plain dict via `environ`.
        environ = {k: cfg_mod.knob(k) for k in (
            "JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
            "TPU_WORKER_HOSTNAMES", "MEGASCALE_COORDINATOR_ADDRESS")}
    env = environ
    if env.get("JAX_COORDINATOR_ADDRESS"):
        return True
    nproc = env.get("JAX_NUM_PROCESSES")
    if nproc:
        try:
            if int(nproc) > 1:
                return True
            # N=1 is semantically single-process (e.g. a pod launcher
            # template run on one host) — not a distributed topology
        except ValueError:
            return True  # malformed: surface initialize's fatal error
    hosts = [h for h in env.get("TPU_WORKER_HOSTNAMES", "").split(",")
             if h.strip()]
    if len(hosts) > 1:
        return True
    if env.get("MEGASCALE_COORDINATOR_ADDRESS"):
        return True
    return False


def maybe_initialize_distributed() -> None:
    """Multi-host bring-up (SURVEY.md §2c multi-node gap): the reference is
    single-node only (`torchrun --standalone`, multi-gpu/ddp/train.sh:49);
    its torchrun path always rendezvouses (multi-gpu/ddp/train.py:19-25) —
    this must be equally reliable on TPU pods, with no launcher-specific
    env required.

    Ordering matters (round-1 bug): any backend probe — even
    `jax.process_count()` — initializes the local backend, after which
    `jax.distributed.initialize()` is too late and N processes silently run
    disconnected. So the gate reads ONLY environment variables, and the
    pre-init check is the public `jax.distributed.is_initialized()` (client
    state, touches no backend)."""
    if not multihost_env_detected():
        return
    from distributed_pytorch_tpu import compat
    if compat.distributed_is_initialized():
        return
    # jax.distributed.initialize() auto-detects only TPU-pod / Slurm / MPI
    # environments; the explicit JAX_* env convention (our launchers, and
    # the round-4 two-process CPU test that caught this) must be passed as
    # arguments or initialize raises "Number of processes must be defined".
    #
    # Failure here is FATAL: the env announced a multi-process topology, so
    # continuing single-process would have N hosts training disconnected on
    # the full dataset and race-writing the same checkpoints — the silent
    # failure mode this function exists to prevent. The reference's
    # torchrun path likewise rendezvouses or dies (ddp/train.py:19-25).
    try:
        kwargs = {}
        if cfg_mod.knob("JAX_COORDINATOR_ADDRESS"):
            kwargs["coordinator_address"] = \
                cfg_mod.knob("JAX_COORDINATOR_ADDRESS")
        if cfg_mod.knob("JAX_NUM_PROCESSES"):
            kwargs["num_processes"] = int(cfg_mod.knob("JAX_NUM_PROCESSES"))
        if cfg_mod.knob("JAX_PROCESS_ID"):
            kwargs["process_id"] = int(cfg_mod.knob("JAX_PROCESS_ID"))
        jax.distributed.initialize(**kwargs)
    except Exception as e:
        raise RuntimeError(
            "[dist] multi-process environment detected but "
            f"jax.distributed.initialize failed: {e}. Refusing to continue "
            "single-process (hosts would train disconnected). Check "
            "JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID "
            "(all hosts need distinct integer process ids) or unset them "
            "for a single-process run.") from e


def _data_paths(train_cfg: TrainConfig, vocab_size: int) -> tuple[str, str]:
    d = os.path.join(train_cfg.data_dir, train_cfg.dataset)
    train_bin = os.path.join(d, "train.bin")
    val_bin = os.path.join(d, "val.bin")
    if train_cfg.dataset == "synthetic" and os.path.exists(train_bin):
        # A synthetic bin left by a previous run with a LARGER vocab feeds
        # out-of-range token ids -> silent NaN loss (found by a round-4
        # verify run). Probe a prefix and regenerate on mismatch; a
        # corrupt/empty file (pre-atomic-write leftovers) counts as a
        # mismatch rather than a crash.
        try:
            probe = np.memmap(train_bin, dtype=np.uint16, mode="r")
            stale = int(probe[:65536].max()) >= vocab_size
            del probe
        except (ValueError, OSError):
            stale = True
        if stale:
            for p in (train_bin, val_bin):
                try:
                    os.remove(p)
                except FileNotFoundError:
                    pass  # another host on a shared data_dir won the race
    if not os.path.exists(train_bin):
        if train_cfg.dataset == "synthetic":
            make_synthetic_bin(train_bin, n_tokens=2 ** 21,
                               vocab_size=vocab_size)
            make_synthetic_bin(val_bin, n_tokens=2 ** 17, seed=271828,
                               vocab_size=vocab_size)
        else:
            raise FileNotFoundError(
                f"{train_bin} not found — run "
                f"python -m distributed_pytorch_tpu.data.prepare_"
                f"{train_cfg.dataset} (or use --dataset synthetic)")
    return train_bin, val_bin


@contextlib.contextmanager
def _graceful_stop():
    """Preemption-safe shutdown (SURVEY §5: the reference has no failure
    handling at all — torchrun without --max-restarts, no signal handling).
    On SIGTERM — what Cloud TPU preemptible/spot VMs send before reclaim —
    or SIGINT — Ctrl-C on a dev box, which previously killed the process
    through KeyboardInterrupt and lost everything since the last
    checkpoint (ISSUE 13 satellite) — set a flag the training loop checks
    (and AGREES on across processes, see _agree_stop) at the top of each
    iteration, where it writes a checkpoint and exits cleanly; with
    `--resume` the next run continues the exact stream. Installed only
    from the main thread (signal API constraint); restores the previous
    handlers on exit.

    The handler body ONLY sets a flag: calling print/log from a signal
    handler can re-enter a locked stdout buffer mid-write and raise
    RuntimeError in the main thread — the loop logs the event instead."""
    stop = {"flag": False, "signame": ""}
    prevs: list[tuple[int, object]] = []
    if threading.current_thread() is threading.main_thread():
        def _handler(signum, frame):
            stop["flag"] = True
            stop["signame"] = signal.Signals(signum).name
        for signum in (signal.SIGTERM, signal.SIGINT):
            try:
                prevs.append((signum, signal.signal(signum, _handler)))
            except ValueError:  # pragma: no cover - embedded interpreters
                pass
    try:
        yield stop
    finally:
        # prev is None when the previous handler was installed from C
        # (not inspectable from Python) — leave ours in place then
        for signum, prev in prevs:
            if prev is not None:
                signal.signal(signum, prev)


def _agree_stop(local_flag: bool) -> bool:
    """Cross-process agreement on the preemption flag: only the SIGTERM'd
    host sees it locally, but every control-flow divergence on a pod —
    skipping an eval, entering the checkpoint save (an orbax cross-process
    collective), breaking the loop — must happen on ALL processes in the
    same iteration or the slice deadlocks on mismatched collectives. A
    tiny allgather-any per iteration buys that agreement; single-process
    runs skip it entirely."""
    if jax.process_count() == 1:
        return local_flag
    from jax.experimental import multihost_utils
    flags = multihost_utils.process_allgather(
        np.asarray([local_flag], dtype=np.bool_))
    return bool(np.asarray(flags).any())


def _prune_ckpts(ckpt_root: str, train_cfg: TrainConfig, say) -> None:
    """Retention after a save (ISSUE 13 satellite): keep the newest K
    verified step dirs. K = --keep_ckpts when set, else the
    TRAIN_KEEP_CKPTS knob; 0 (the default) keeps everything. Only
    manifest-verified dirs are eligible and the newest good one always
    survives (train/checkpoint.py::prune_checkpoints)."""
    keep = train_cfg.keep_ckpts if train_cfg.keep_ckpts > 0 \
        else cfg_mod.knob("TRAIN_KEEP_CKPTS")
    if keep > 0:
        for d in ckpt.prune_checkpoints(ckpt_root, keep):
            say(f"retention: pruned {d} (keeping newest {keep})")


def _atomic_write_json(path: str, obj: dict) -> None:
    """tmp + rename so a reader — or a preemption mid-write — never
    sees a torn stats.json (the write is refreshed at every checkpoint
    boundary, not just at exit)."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f, indent=1)
    os.replace(tmp, path)


def _refresh_memplan(stats: dict, predicted_gb, breakdown) -> None:
    """(Re)sample the per-device HBM watermark against the memplan
    prediction into stats['memplan'] — the ROADMAP validation record:
    `{memplan_predicted_gb, measured_peak_gb, delta}` per device."""
    stats["memplan"] = {
        "predicted_gb": round(predicted_gb, 3)
        if predicted_gb is not None else None,
        "breakdown_gb": breakdown,
        "devices": memplan.watermark_report(predicted_gb),
    }


def _write_stats_files(stats: dict, model_cfg: LLMConfig,
                       train_cfg: TrainConfig, ckpt_root: str,
                       run_dir: str, predicted_gb, breakdown) -> str:
    """Persist the run record atomically to BOTH homes: the checkpoint
    dir (the reference `<name>_stats.pt` contract, train resume
    tooling) and runs/<run>/ next to train_timeline.jsonl (the round-14
    artifact convention CI uploads)."""
    _refresh_memplan(stats, predicted_gb, breakdown)
    record = {k: v for k, v in stats.items() if k != "state"}
    record["model_config"] = dataclasses.asdict(model_cfg)
    record["train_config"] = dataclasses.asdict(train_cfg)
    path = os.path.join(ckpt_root, "stats.json")
    _atomic_write_json(path, record)
    _atomic_write_json(os.path.join(run_dir, "stats.json"), record)
    return path


def _state_bytes_per_device(state) -> dict:
    """{device id: bytes of the train state resident there} — params and
    optimizer moments, summed over each array's addressable shards. Under
    fsdp on four chips each holds about a quarter; under dp each holds it
    all. Shard sizes, so it reads the same on every backend (the CPU
    reports no memory_stats)."""
    out: dict[int, int] = {}
    for leaf in jax.tree_util.tree_leaves(state):
        for shard in getattr(leaf, "addressable_shards", ()):
            out[shard.device.id] = (out.get(shard.device.id, 0)
                                    + shard.data.nbytes)
    return {str(k): out[k] for k in sorted(out)}


def estimate_loss(eval_step, state, loaders: dict, eval_iters: int) -> dict:
    """Mean eval loss over eval_iters batches per split (reference
    estimate_loss, single-gpu/train.py:280-293). Eval batches are keyed on
    the eval-iteration counter k, NOT on the loaders' live counters, so (a)
    the training stream is untouched by eval cadence and (b) every eval
    call scores the same fixed batch set — val curves are comparable
    point-to-point (a deliberate improvement over the reference's fresh
    random batches per eval)."""
    out = {}
    for split, loader in loaders.items():
        losses = []
        for k in range(eval_iters):
            x, y = loader.next_batch(step=k)
            # eval consumes single micro-batches: take accum slot 0
            losses.append(eval_step(state, x[0], y[0]))
        out[split] = float(np.mean(jax.device_get(losses)))
    return out


def train(model_cfg: LLMConfig, train_cfg: TrainConfig,
          log: Callable[[str], None] = print) -> dict[str, Any]:
    """Run the full training job; returns a stats dict (loss curves,
    throughput) — the in-memory equivalent of the reference's
    `<name>_stats.pt` (single-gpu/train.py:363-372)."""
    maybe_initialize_distributed()
    is_main = jax.process_index() == 0
    say = (lambda s: log(s)) if is_main else (lambda s: None)

    if model_cfg.moe:
        # moe_impl lives in both configs (the CLI routes the flag to both,
        # like the reference's act_recomp linking, train.py:189-190). For
        # programmatic callers a non-default TrainConfig value wins, but a
        # default ('dense') never silently downgrades an explicitly
        # scatter-configured model.
        want = train_cfg.moe_impl if train_cfg.moe_impl != "dense" \
            else model_cfg.moe_impl
        if want != model_cfg.moe_impl:
            say(f"moe_impl: TrainConfig overrides model config -> {want}")
            model_cfg = dataclasses.replace(model_cfg, moe_impl=want)

    if train_cfg.pp_size > 1 and model_cfg.pp_stages != train_cfg.pp_size:
        # the pipe mesh axis and the model's stacked-stage count are one
        # decision; the trainer flag wins (same linking pattern as
        # act_recomp, reference train.py:189-190)
        say(f"pp: setting model pp_stages = pp_size = {train_cfg.pp_size}")
        model_cfg = dataclasses.replace(model_cfg,
                                        pp_stages=train_cfg.pp_size)

    mesh = mesh_for(train_cfg.parallelism, tp_size=train_cfg.tp_size,
                    ep_size=train_cfg.ep_size, sp_size=train_cfg.sp_size,
                    pp_size=train_cfg.pp_size, dp_size=train_cfg.dp_size)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_chips = int(np.prod(mesh.devices.shape))
    dev0 = jax.devices()[0]
    say(f"mesh {sizes} over {n_chips} {dev0.platform} device(s) of kind "
        f"{dev0.device_kind!r}; recipe={train_cfg.parallelism}")

    # ---- grad accumulation arithmetic (reference train.py:297-301) -------
    B, T = train_cfg.batch_size, model_cfg.block_size
    b_glob = B * sizes["data"]
    assert train_cfg.total_batch_size % (b_glob * T) == 0, (
        f"total_batch_size {train_cfg.total_batch_size} not divisible by "
        f"B*T*dp = {b_glob * T}")
    grad_accum = train_cfg.total_batch_size // (b_glob * T)
    tokens_per_step = train_cfg.total_batch_size
    say(f"grad_accum={grad_accum} micro-steps of {b_glob}x{T} tokens "
        f"-> {tokens_per_step} tokens/step")

    # ---- data ------------------------------------------------------------
    train_bin, val_bin = _data_paths(train_cfg, model_cfg.vocab_size)
    bspec = shd.batch_pspec(train_cfg.parallelism, mesh, leading_accum=True)
    mk = lambda p, seed: DataLoader(p, b_glob, T, grad_accum=grad_accum,
                                    seed=seed, mesh=mesh, pspec=bspec)
    train_loader = mk(train_bin, train_cfg.seed)
    # Eval gets its OWN loaders/streams: the training batch sequence is
    # invariant to eval cadence (round-1 weak #6 — the reference shares one
    # loader, so eval settings silently change the data order).
    val_loader = mk(val_bin, train_cfg.seed + 1)
    eval_train_loader = mk(train_bin, train_cfg.seed + 2)

    # ---- model / state / steps ------------------------------------------
    model, tx, state, state_sharding = create_train_state(
        model_cfg, train_cfg, mesh)
    total, active = count_params(state.params, model_cfg)
    say(f"params: {total / 1e6:.2f}M total, {active / 1e6:.2f}M active")

    # ---- ZeRO-Offload gate (train/offload.py, ISSUE 19) ------------------
    # OFFLOAD knob / TrainConfig.offload; 'auto' offloads exactly when the
    # in-HBM memplan busts the per-chip budget and the offload plan fits.
    from distributed_pytorch_tpu.train import offload as offload_mod
    offload_on = offload_mod.resolve_offload(model_cfg, train_cfg, sizes)
    if offload_on:
        # the moments live in host RAM from here on: the fresh init moves
        # over now; a checkpoint restore below restores them straight to
        # the host via the per-leaf sharding tree
        state = state.replace(opt_state=jax.device_put(
            state.opt_state, offload_mod.host_device()))
        say("offload: optimizer moments -> host RAM (ZeRO-Offload; update "
            "on host, params streamed back per step)")

    start_step = 0
    ckpt_root = os.path.join("checkpoints", train_cfg.file_name)
    resume_info = None  # (path, skipped) for the telemetry recovery event
    if train_cfg.resume:
        abstract = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), state)
        # restore_latest walks newest→oldest past torn/corrupt step dirs
        # (blake2b manifest verification, train/checkpoint.py) — a flipped
        # byte in the newest save falls back to the previous good one
        # instead of crashing the rejoin (ISSUE 13)
        restore_sharding = (offload_mod.host_state_sharding(state_sharding)
                            if offload_on else state_sharding)
        restored = ckpt.restore_latest(ckpt_root, abstract, restore_sharding)
        if restored is not None:
            state, last, skipped = restored
            start_step = int(jax.device_get(state.step))
            resume_info = (last, skipped)
            for bad in skipped:
                say(f"resume: skipped unusable checkpoint {bad}")
            say(f"resumed from {last} at step {start_step}")

    train_step = make_train_step(model, tx, model_cfg, train_cfg, mesh,
                                 state_sharding, offload=offload_on)
    # AOT program store (parallel/aot_store.py, ISSUE 18): with the
    # AOT_STORE knobs on, the train step is resolved through the store —
    # a hit hands the loop a deserialized executable (restart-to-first-
    # step is then dominated by the checkpoint restore above, not XLA),
    # a miss compiles eagerly and writes back for the next incarnation.
    # The supervisor pre-warms the rung-down key set on re-mesh, so a
    # surviving gang's restart hits.
    from distributed_pytorch_tpu.parallel import aot_store as aot_mod
    _store = aot_mod.resolve_store()
    if _store is not None and offload_on:
        # the offload step is a host-orchestrated pair of programs, not
        # one AOT-serializable executable; skip the store rather than
        # cache a step that isn't the one running
        say("aot store: skipped (offload step is not a single program)")
        _store = None
    if _store is not None:
        train_step = aot_mod.wrap_train_step(
            _store, train_step, state, model_cfg, train_cfg, mesh,
            grad_accum=grad_accum, b_glob=b_glob)
        say(f"aot store: train_step "
            f"{'hit' if _store.hits else 'miss'} "
            f"(hits={_store.hits} misses={_store.misses} "
            f"compile_ms={_store.compile_ms:.0f} root={_store.root})")
    # eval never touches the optimizer state; with offload the moments sit
    # on the host and a TrainState-shaped in_shardings would drag 2x-params
    # of bytes back through PCIe every eval — so the eval program sees a
    # view of the state with opt_state stripped (and a matching sharding).
    if offload_on:
        eval_sharding = state_sharding.replace(opt_state=())
        eval_view = lambda s: s.replace(opt_state=())  # noqa: E731
    else:
        eval_sharding = state_sharding
        eval_view = lambda s: s  # noqa: E731
    eval_step = make_eval_step(model, train_cfg, mesh, eval_sharding)

    # ---- loop ------------------------------------------------------------
    stats = {"train_losses": [], "val_losses": [], "step_times": [],
             "tokens_per_sec": [], "mfu": []}
    if model_cfg.moe:
        stats["moe_dropped_frac"] = []  # per-synced-step drop fractions
    flops_per_step = M.step_flops(model_cfg, tokens_per_step, T)
    peak = M.peak_flops_per_chip()

    # ---- training observability (train/telemetry.py, ISSUE 10) ----------
    # All feeding happens at the existing sync boundaries (the drain
    # below already blocks on the queued metric futures), so the
    # per-step hot path stays device-async; telemetry=False reduces
    # every call site to one attribute check, no allocation.
    tel = telemetry.TrainTelemetry(
        run=train_cfg.file_name, enabled=train_cfg.telemetry,
        anomaly=train_cfg.anomaly)
    run_dir = os.path.join("runs", train_cfg.file_name)
    timeline_path = os.path.join(run_dir, "train_timeline.jsonl")
    if tel.enabled and resume_info is not None:
        # recovery event on the timeline/metrics (ISSUE 13): which step
        # dir the run rejoined from, and how many unusable (torn or
        # corrupt) dirs the manifest walk skipped to get there
        last, skipped = resume_info
        tel.metrics.inc("resumes")
        if skipped:
            tel.metrics.inc("ckpt_fallbacks", len(skipped))
        tel.record_step(event="resume", it=start_step,
                        ckpt=os.path.basename(last),
                        fallbacks=len(skipped))
    # price the config ACTUALLY in flight once up front; the
    # peak_bytes_in_use watermark is sampled at boundaries below and
    # the delta lands in the timeline, stats.json, and bench JSON
    try:
        memplan_pred_gb, memplan_breakdown = \
            memplan.predicted_train_peak_gb(model_cfg, train_cfg, sizes,
                                            offload=offload_on)
    except Exception as e:  # noqa: BLE001 — planning never stops a run
        memplan_pred_gb, memplan_breakdown = None, {"error": repr(e)}
    # 1f1b schedule record (ISSUE 19): the static (tick, stage, chunk,
    # phase) timeline + bubble summary for the run's actual S/vpp/M —
    # what the CPU A/B test checks against the (S-1)/(vpp*M) model, and
    # what a chip run compares the profiler trace to. Static table, no
    # device work; per-phase rows only for small tables.
    if model_cfg.pp_stages > 1:
        from distributed_pytorch_tpu.models import pipeline as pipe_mod
        if pipe_mod.resolve_schedule(model_cfg) == "1f1b":
            S = model_cfg.pp_stages
            vpp = pipe_mod.resolve_vpp(model_cfg)
            Mpp = model_cfg.pp_microbatches
            if Mpp <= 0:  # mirror run_pipeline's auto pick
                Mpp = min(b_glob, 2 * S)
                while b_glob % Mpp:
                    Mpp -= 1
            sched_rows, sched_sum = pipe_mod.schedule_timeline(S, vpp, Mpp)
            say(f"pp schedule: 1f1b S={S} vpp={vpp} M={Mpp} | bubble "
                f"{sched_sum['bubble_frac']:.3f} (model (S-1)/(vpp*M)="
                f"{sched_sum['bubble_model']:.3f})")
            if tel.enabled:
                tel.record_step(event="pp_schedule", it=start_step,
                                **sched_sum)
                if len(sched_rows) <= 256:
                    for r in sched_rows:
                        tel.record_step(event="pp_phase", it=start_step,
                                        **r)
    # device-free spec-table validation (parallel/shardcheck.py): surface
    # sharding mistakes — replicated-large, dead axes — at startup, where
    # they cost a log line instead of an OOM'd or silently slow run.
    # Advisory like memplan: findings never stop a run. Skipped for
    # 'single' (nothing is sharded, and the eval_shape pass would tax
    # every tiny unsharded test run for no findings).
    if train_cfg.parallelism != "single":
        try:
            from distributed_pytorch_tpu.parallel import shardcheck
            sc = shardcheck.check_train_config(model_cfg, train_cfg)
            if sc.findings and is_main:
                say(shardcheck.format_report(sc))
        except Exception as e:  # noqa: BLE001
            if is_main:
                say(f"shardcheck skipped: {e!r}")
    # an anomaly event's data-shard coordinates: the loader is
    # step-keyed, so these + batch_step reproduce the poisoned batch
    data_coords = {"dataset": train_cfg.dataset, "seed": train_cfg.seed,
                   "dp_shards": sizes.get("data", 1)}
    tel.metrics.set_build_info(
        run=train_cfg.file_name, recipe=train_cfg.parallelism,
        model=f"L{model_cfg.n_layer}xD{model_cfg.n_embd}-{model_cfg.attn}",
        tokens_per_step=tokens_per_step, grad_accum=grad_accum,
        anomaly=train_cfg.anomaly, jax=jax.__version__)
    tel_server = None
    if train_cfg.metrics_port >= 0 and is_main and tel.enabled:
        # opt-in live endpoint (main host only): a multi-hour TPU run
        # is inspectable mid-flight without killing it. Daemon thread —
        # an exception path that skips stop() cannot hold the process.
        tel_server = telemetry.TelemetryServer(
            tel, port=train_cfg.metrics_port).start()
        stats["telemetry_port"] = tel_server.port
        say(f"telemetry: http://127.0.0.1:{tel_server.port}/metrics "
            f"(step records at /debug/timeline, liveness at /healthz)")
    elif train_cfg.metrics_port >= 0 and is_main:
        say("metrics_port set but --no-telemetry: endpoint not started")

    # on-demand device profiling routed through the shared obs/profile.py
    # wrapper (the old hardcoded "profile_trace" dir is gone): captures
    # land under runs/<run>/profile unless --profile_dir says otherwise,
    # alongside the rest of the run's artifacts
    prof_dir = None
    if train_cfg.profile and is_main:
        from distributed_pytorch_tpu.obs import profile as obs_profile
        prof_dir = obs_profile.start_profile(
            train_cfg.profile_dir or None, run=train_cfg.file_name)
        say(f"profiler tracing -> {prof_dir}")

    # Training batches are keyed on the iteration number, so a resumed run
    # continues the exact uninterrupted stream (round-1 weak #4: the loader
    # was step-keyed but never fast-forwarded on resume).
    #
    # Sync discipline (round-4 MFU work): the host blocks on step metrics
    # only at log/eval/checkpoint boundaries, not every iteration — between
    # boundaries, steps are dispatched back-to-back and their metric
    # futures queue up, so host->device round-trip latency overlaps device
    # compute instead of serializing with it. The reference syncs every step
    # (torch.cuda.synchronize, single-gpu/train.py:355) — an intentional
    # divergence. Per-step dt is the boundary window's average.
    # retrace guard (obs/retrace.py): the first call may trace, every
    # later iteration must reuse the compiled step — expect(0) pins a
    # mid-run recompile to the iteration that caused it, and the guard's
    # count/excess are exported as train_retraces gauges below.
    step_guard = getattr(train_step, "trace_guard", None)
    if step_guard is not None and tel.enabled:
        tel.metrics.register_gauge(
            "train_step_traces_total", lambda: float(step_guard.count),
            "compiled train-step traces (budget 1; more = recompile cliff)")
        tel.metrics.register_gauge(
            "train_step_retrace_excess", lambda: float(step_guard.excess),
            "train-step traces past budget — should be 0")

    x, y = train_loader.next_batch(step=start_step)
    stats["device"] = obs_paths.device_record()
    stats["hbm_after_init"] = M.hbm_watermark()
    stats["state_bytes_per_device"] = _state_bytes_per_device(state)
    if hasattr(train_step, "lower") and not offload_on and _store is None:
        # compile the step NOW and say what is in it before it runs: which
        # Pallas kernels (the compiled text's tpu_custom_calls, by name),
        # which collectives, what the dispatchers chose, and the compiler's
        # own memory accounting. The jit call below reuses this executable
        # (same lowering cache), so the cost is the compile the first step
        # would have paid anyway — now timed on its own.
        prog = obs_paths.compile_and_describe(train_step, state, x, y)
        stats["programs"] = {"train.step": prog}
        say(f"[program] train.step: compiled in {prog['compile_s']:.1f}s | "
            f"kernels {prog['kernels'] or 'none (XLA only)'} | "
            f"collectives {prog['collectives'] or 'none'} | "
            f"paths {prog['paths']} | temp "
            f"{prog.get('temp_bytes', 0) / 2 ** 30:.2f} GiB, args "
            f"{prog.get('argument_bytes', 0) / 2 ** 30:.2f} GiB")
    pending: list = []                         # metric futures since last sync
    win_t0 = time.perf_counter()
    # the iteration's host phases (obs/trace.py PHASES): leaves in the
    # profiler's trace, and this window's seconds per phase for the
    # timeline records (data_ms, dispatch_ms, sync_ms)
    win: dict = {}
    # the flight recorder's turn (obs/flight.py): one drained log window,
    # from the last boundary's record to this one's, evals and the
    # checkpoint between them included; the first window and any retrace
    # are its `compile`
    traces_seen = 0
    if tel.enabled:
        tel.flight.begin_turn()
    stopped_early = False
    with _graceful_stop() as stop:
        for it in range(start_step, train_cfg.max_iters + 1):
            # Preemption checks happen at DETERMINISTIC boundaries (every
            # process computes the same schedule from it/config): on pods
            # _agree_stop is a collective, and running it every iteration
            # would re-serialize the async step pipeline this loop exists
            # to avoid. Worst-case reaction latency = log_interval steps.
            check_due = (it == start_step
                         or it % train_cfg.log_interval == 0
                         or (train_cfg.eval
                             and it % train_cfg.eval_interval == 0))
            if check_due and _agree_stop(stop["flag"]):
                # preemption: drain queued metrics, checkpoint the state as
                # of the last completed step, exit before spending grace
                # time on eval or another step
                if pending:
                    for g in jax.device_get(pending):
                        stats["train_losses"].append(float(g["loss"]))
                    pending.clear()
                step_now = int(jax.device_get(state.step))
                ckpt.wait_for_saves()  # in-flight async save first
                path = ckpt.save_checkpoint(
                    os.path.join(ckpt_root, f"step_{step_now}"), state,
                    model_cfg, train_cfg)
                say(f"[signal] {stop['signame'] or 'SIGTERM'}: checkpoint "
                    f"-> {path}; stopping at iter {it} "
                    f"(resume with --resume)")
                stopped_early = True
                break

            if train_cfg.eval and it % train_cfg.eval_interval == 0:
                with phase("train.eval", win, step=it):
                    t0 = time.perf_counter()
                    ev = estimate_loss(eval_step, eval_view(state),
                                       {"train": eval_train_loader,
                                        "val": val_loader},
                                       train_cfg.eval_iters)
                    stats["val_losses"].append((it, ev["val"]))
                    if tel.enabled:
                        tel.metrics.inc("evals")
                    say(f"iter {it}: train {ev['train']:.4f} val "
                        f"{ev['val']:.4f} ({time.perf_counter() - t0:.1f}s)")
                win_t0 = time.perf_counter()       # eval time isn't step time

            with phase("train.dispatch", win, step=it):
                if step_guard is not None:
                    with step_guard.expect(0 if step_guard.count else 1):
                        state, m = train_step(state, x, y)
                else:
                    state, m = train_step(state, x, y)
            pending.append(m)
            if it < train_cfg.max_iters:  # no wasted sample on the final iter
                with phase("train.data", win, step=it):
                    # host prefetch while the device runs
                    x, y = train_loader.next_batch(step=it + 1)

            ckpt_due = bool(train_cfg.ckpt_interval and it
                            and it % train_cfg.ckpt_interval == 0)
            eval_next = (train_cfg.eval
                         and (it + 1) % train_cfg.eval_interval == 0)
            sync_due = (it % train_cfg.log_interval == 0 or ckpt_due
                        or eval_next or it == train_cfg.max_iters)
            if sync_due:
                with phase("train.sync", win, step=it,
                           n_steps=len(pending)):
                    got = jax.device_get(pending)  # blocks on all queued steps
                t_now = time.perf_counter()
                sync_s = win["train.sync"]         # host blocked on the drain
                dt = (t_now - win_t0) / len(pending)
                win_t0 = t_now
                with phase("train.drain", step=it) as draining:
                    n_got = len(got)               # window is contiguous iters
                    data_s = win.get("train.data", 0.0) / n_got
                    first_window = not stats["train_losses"]
                    win_first_it = it - n_got + 1
                    for g in got:
                        stats["train_losses"].append(float(g["loss"]))
                        if "moe_dropped_frac" in g:
                            stats["moe_dropped_frac"].append(
                                float(g["moe_dropped_frac"]))
                    pending.clear()
                    if not first_window:    # first window includes compile
                        for _ in got:
                            stats["step_times"].append(dt)
                            stats["tokens_per_sec"].append(
                                tokens_per_step / dt)
                            if peak:
                                stats["mfu"].append(
                                    flops_per_step / dt / (peak * n_chips))
                    # ---- anomaly + telemetry drain: the boundary already
                    # paid the device sync; everything below is host floats
                    mfu_now = (flops_per_step / dt / (peak * n_chips)
                               if peak else None)
                    # watermark: compile is in the first window's sample
                    hbm_now = M.device_memory_gb()
                    for k, g in enumerate(got):
                        it_k = win_first_it + k
                        loss_k = float(g["loss"])
                        gn_k = float(g["grad_norm"])
                        ev = tel.anomalies.observe(
                            it=it_k, loss=loss_k, grad_norm=gn_k,
                            skipped=bool(g.get("update_skipped", 0.0)),
                            coords={**data_coords, "batch_step": it_k})
                        if ev is not None:
                            tel.record_anomaly(ev)
                            stats.setdefault("anomalies", []).append(ev)
                            skip_s = (", update skipped" if ev["skipped"]
                                      else "")
                            say(f"[anomaly] iter {it_k}: {ev['kind']} "
                                f"(loss {loss_k:.4g}, grad_norm {gn_k:.4g}"
                                f"{skip_s}) — batch from "
                                f"{ev.get('data_coords')}")
                        if tel.enabled:
                            rec = {"it": it_k, "loss": loss_k,
                                   "grad_norm": gn_k,
                                   "data_ms": round(data_s * 1e3, 3)}
                            if first_window:   # compile-inclusive window:
                                rec["compile_window"] = True  # no step_ms
                            else:
                                rec["step_ms"] = round(dt * 1e3, 3)
                                rec["tokens_per_s"] = round(
                                    tokens_per_step / dt, 1)
                                if mfu_now is not None:
                                    rec["mfu"] = round(mfu_now, 4)
                            if k == n_got - 1:
                                # the boundary record carries the drain,
                                # the window's mean enqueue and the
                                # watermark
                                rec["sync_ms"] = round(sync_s * 1e3, 3)
                                rec["dispatch_ms"] = round(
                                    win["train.dispatch"] / n_got * 1e3, 3)
                                if hbm_now:
                                    rec["hbm_gb"] = round(hbm_now, 3)
                                t_rec = time.perf_counter()
                                parts = {name[len("train."):]: s * 1e3
                                         for name, s in win.items()}
                                parts["drain"] = (t_rec - draining.t0) * 1e3
                                traces = (step_guard.count
                                          if step_guard is not None else 0)
                                tel.flight.record_turn(
                                    "train", parts, t_rec,
                                    compiled=(first_window
                                              or traces != traces_seen),
                                    **rec)
                                traces_seen = traces
                            else:
                                tel.record_step(**rec)
                    if tel.enabled:
                        tel.metrics.inc("steps", n_got)
                        tel.metrics.observe_phases(
                            step_s=None if first_window else dt,
                            data_s=data_s, sync_s=sync_s)
                        tel.last.update(
                            it=it, loss=float(got[-1]["loss"]),
                            tokens_per_s=(0.0 if first_window
                                          else tokens_per_step / dt),
                            mfu=None if first_window else mfu_now,
                            hbm_gb=hbm_now)
                    win.clear()
                    if it % train_cfg.log_interval == 0:
                        loss = stats["train_losses"][-1]
                        tps = tokens_per_step / dt
                        mfu_s = (f" | mfu {mfu_now:6.2%}" if peak else "")
                        # reference reserved-GB print (train.py:356);
                        # hbm_now was sampled at this same boundary above
                        hbm_s = f" | hbm {hbm_now:5.2f}GB" if hbm_now else ""
                        drop_s = ""
                        if stats.get("moe_dropped_frac"):
                            # silent GShard-style drops (scatter mode)
                            # become a visible per-step number;
                            # dense/grouped print 0
                            drop_s = (f" | moe_drop "
                                      f"{stats['moe_dropped_frac'][-1]:6.2%}")
                        say(f"iter {it:5d} | loss {loss:.4f} | "
                            f"dt {dt * 1e3:7.1f}ms | "
                            f"tok/s/chip {tps / n_chips:10.0f}{mfu_s}{hbm_s}"
                            f"{drop_s}")

            if ckpt_due:
                with phase("train.ckpt", win, step=it):
                    # interval saves are async: serialization overlaps the next
                    # steps instead of stalling them (train/checkpoint.py)
                    path = ckpt.save_checkpoint_async(
                        os.path.join(ckpt_root, f"step_{it}"), state,
                        model_cfg, train_cfg)
                    # the pre-save snapshot copy is the one synchronous cost an
                    # async save keeps; track it so the 1.5B step-time dent is
                    # visible (ROADMAP async-checkpoint item)
                    stats.setdefault("ckpt_snapshot_ms", []).append(
                        round(ckpt.last_snapshot_ms, 2))
                    if tel.enabled:
                        tel.metrics.inc("checkpoints")
                        tel.metrics.observe_phases(
                            ckpt_s=ckpt.last_snapshot_ms / 1e3)
                        tel.record_step(
                            event="ckpt", it=it,
                            ckpt_ms=round(ckpt.last_snapshot_ms, 2))
                    # refresh the on-disk run record at EVERY checkpoint
                    # boundary (atomic tmp+rename): a preempted or killed
                    # run leaves a usable stats.json + timeline behind, not
                    # only the copy written at exit
                    if train_cfg.save_stats and is_main:
                        _write_stats_files(stats, model_cfg, train_cfg,
                                           ckpt_root, run_dir,
                                           memplan_pred_gb, memplan_breakdown)
                    if tel.enabled and is_main:
                        tel.dump(timeline_path)
                    say(f"checkpoint (async) -> {path} "
                        f"(snapshot {ckpt.last_snapshot_ms:.0f}ms)")
                    # retention: this save's manifest is still pending (its
                    # durability lands at the next wait), so pruning here only
                    # ever deletes OLDER verified dirs — the in-flight one is
                    # untouchable by construction
                    _prune_ckpts(ckpt_root, train_cfg, say)
                win_t0 = time.perf_counter()       # ckpt time isn't step time

    if train_cfg.profile and is_main:
        from distributed_pytorch_tpu.obs import profile as obs_profile
        obs_profile.stop_profile()
        say(f"profiler trace -> {prof_dir} (device time by scope and idle "
            f"time by train.* phase: scripts/profile_step.py "
            f"--analyze_only --trace_dir {prof_dir})")
        stats["profile_dir"] = prof_dir

    ckpt.wait_for_saves()  # async interval saves must be durable

    # the preemption branch already wrote this exact state; a second
    # blocking save would burn the remaining grace period on redundant I/O
    if train_cfg.save_model and not stopped_early:
        final = int(jax.device_get(state.step))
        path = ckpt.save_checkpoint(
            os.path.join(ckpt_root, f"step_{final}"), state,
            model_cfg, train_cfg)
        say(f"final checkpoint -> {path}")
    _prune_ckpts(ckpt_root, train_cfg, say)  # after-save retention pass

    stats["final_loss"] = stats["train_losses"][-1] if stats["train_losses"] else None
    stats["peak_hbm_gb"] = M.device_memory_gb()
    # every counter the runtime keeps for device 0, as it reports them
    # (None on the CPU backend)
    stats["memory_stats"] = jax.local_devices()[0].memory_stats()
    _refresh_memplan(stats, memplan_pred_gb, memplan_breakdown)
    if step_guard is not None:
        # budget 1: anything above is a recompile after the first step
        stats["step_traces"] = step_guard.count
        stats["step_retraces"] = step_guard.excess
    if stats["peak_hbm_gb"] is not None:
        pred = ("no prediction" if memplan_pred_gb is None
                else f"{memplan_pred_gb:.2f} GiB predicted")
        say(f"peak HBM {stats['peak_hbm_gb']:.2f} GiB measured (in use + "
            f"reserved) vs memplan {pred}; train.step traces "
            f"{stats.get('step_traces')} (retraces after the first: "
            f"{stats.get('step_retraces')})")
    if tel.enabled and is_main:
        # the step-phase timeline next to the rest of the run artifacts
        stats["artifacts"] = {"train_timeline": tel.dump(timeline_path)}
    if stats.get("anomalies"):
        stats["n_anomalies"] = len(stats["anomalies"])
    if stats.get("moe_dropped_frac"):
        # headline number for bench JSON: the steady-state drop fraction
        stats["final_moe_dropped_frac"] = stats["moe_dropped_frac"][-1]
    if stats["step_times"]:
        med = float(np.median(stats["step_times"]))
        stats["median_step_time"] = med
        stats["median_tokens_per_sec"] = tokens_per_step / med
        stats["median_mfu"] = (flops_per_step / med / (peak * n_chips)
                               if peak else None)
    stats["params_total"], stats["params_active"] = int(total), int(active)

    if train_cfg.save_stats and is_main:
        # JSON-persisted run record (the reference's `<name>_stats.pt`,
        # single-gpu/train.py:361-372, which round 1 let evaporate) —
        # written atomically, and already refreshed at every checkpoint
        # boundary above so this is only the final state of it.
        stats_path = _write_stats_files(stats, model_cfg, train_cfg,
                                        ckpt_root, run_dir,
                                        memplan_pred_gb, memplan_breakdown)
        say(f"stats -> {stats_path}")

    if tel_server is not None:
        tel_server.stop()

    stats["state"] = state
    return stats
