"""Elastic training (ISSUE 13): verified checkpoints (blake2b manifest,
torn-dir skip, corrupt fallback, retention) and the host-failure
supervisor's state machine (kill→gang restart/rejoin, heartbeat-timeout
detection, held-dead host→rung-down re-mesh).

Supervisor tests drive the REAL Supervisor watch loop against stub
worker processes (heartbeat + exit protocol only, no jax import per
worker) so they stay tier-1 sized; the full 2-process JAX kill/re-mesh
end-to-end lives in scripts/fault_inject_train.py (CI smoke leg)."""

import json
import os
import signal
import sys
import textwrap
import threading
import time

import jax
import pytest

from distributed_pytorch_tpu.config import LLMConfig, TrainConfig
from distributed_pytorch_tpu.parallel.mesh import rung_down
from distributed_pytorch_tpu.train import checkpoint as ckpt
from distributed_pytorch_tpu.train import supervisor as sup
from distributed_pytorch_tpu.train.loop import train

TINY = dict(vocab_size=256, block_size=32, n_embd=32, n_head=4,
            n_kv_heads=4, n_layer=2, up_dim=64)


def _tc(**kw):
    base = dict(dataset="synthetic", data_dir="bench_data",
                total_batch_size=2 * 2 * 32, batch_size=2,
                max_iters=5, parallelism="single", eval=False,
                log_interval=100, save_stats=False, learning_rate=1e-3,
                warmup_steps=2)
    base.update(kw)
    return TrainConfig(**base)


_STARTED: list = []     # (supervisor, its thread) of the test that runs


@pytest.fixture()
def in_tmp(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    yield tmp_path
    # a supervisor that a failed test left running is stopped and joined
    # BEFORE the chdir is undone: its run directory is relative
    # (`runs/<run_name>`), so one that outlived its test would write its
    # state, heartbeats and timeline into the repository
    while _STARTED:
        s, t = _STARTED.pop()
        s._stop = True
        t.join(timeout=30)
        assert not t.is_alive(), "a supervisor thread outlived its test"


# ---------------------------------------------------------------------------
# Verified checkpoints.
# ---------------------------------------------------------------------------

def _mk_step(root, n, payload=b"x" * 256, manifest=True):
    """Hand-build one step dir: state/ payload + config.json
    (+ manifest)."""
    d = os.path.join(root, f"step_{n}")
    os.makedirs(os.path.join(d, "state"), exist_ok=True)
    with open(os.path.join(d, "state", "data.bin"), "wb") as f:
        f.write(payload)
    with open(os.path.join(d, "config.json"), "w") as f:
        json.dump({"step": n}, f)
    if manifest:
        ckpt.write_manifest(d)
    return d


def test_manifest_roundtrip_detects_flipped_byte(in_tmp):
    root = "ck"
    d = _mk_step(root, 10)
    assert ckpt.verify_manifest(d) == []
    assert ckpt.verify_manifest(d, deep=False) == []
    # flip one byte: size unchanged, so only the DEEP check can see it
    with open(os.path.join(d, "state", "data.bin"), "r+b") as f:
        f.seek(17)
        b = f.read(1)
        f.seek(17)
        f.write(bytes([b[0] ^ 0xFF]))
    assert ckpt.verify_manifest(d, deep=False) == []
    deep = ckpt.verify_manifest(d)
    assert deep and "blake2b mismatch" in deep[0]


def test_latest_step_dir_skips_torn_dirs(in_tmp):
    root = "ck"
    good = _mk_step(root, 1)
    # torn: orbax state/ never finalized (empty) — the crash-mid-async
    # shape; config.json exists because it is written eagerly
    torn = os.path.join(root, "step_2")
    os.makedirs(os.path.join(torn, "state"))
    with open(os.path.join(torn, "config.json"), "w") as f:
        json.dump({}, f)
    # truncated: manifest written, then a payload file lost bytes
    trunc = _mk_step(root, 3)
    with open(os.path.join(trunc, "state", "data.bin"), "r+b") as f:
        f.truncate(10)
    assert ckpt.latest_step_dir(root) == os.path.abspath(good)
    # legacy pre-manifest dirs (structurally complete) are still accepted
    legacy = _mk_step(root, 4, manifest=False)
    assert ckpt.latest_step_dir(root) == os.path.abspath(legacy)


def test_corrupt_newest_falls_back_to_previous_good(in_tmp):
    """Acceptance criterion: a flipped byte in the newest checkpoint is
    detected by the manifest and restore falls back to the previous good
    step dir with no operator intervention."""
    mc = LLMConfig(**TINY)
    stats = train(mc, _tc(max_iters=6, file_name="ver", ckpt_interval=2),
                  log=lambda s: None)
    root = os.path.join("checkpoints", "ver")
    last = ckpt.latest_step_dir(root)
    assert last is not None
    assert ckpt.verify_manifest(last) == []  # async saves got manifests

    # flip a byte in the newest dir's largest payload file
    victim, size = None, 0
    for dirpath, _, files in os.walk(last):
        for name in files:
            p = os.path.join(dirpath, name)
            if name != "manifest.json" and os.path.getsize(p) > size:
                victim, size = p, os.path.getsize(p)
    with open(victim, "r+b") as f:
        f.seek(size // 2)
        b = f.read(1)
        f.seek(size // 2)
        f.write(bytes([b[0] ^ 0xFF]))

    abstract = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), stats["state"])
    with pytest.raises(ckpt.CheckpointCorrupt):
        ckpt.restore_checkpoint(last, abstract)
    res = ckpt.restore_latest(root, abstract)
    assert res is not None
    state, path, skipped = res
    assert path != last and any(last in s for s in skipped)
    assert int(jax.device_get(state.step)) < \
        int(jax.device_get(stats["state"].step))

    # ...and a full resume through the trainer lands on the fallback
    resumed = train(mc, _tc(max_iters=6, file_name="ver", resume=True),
                    log=lambda s: None)
    assert resumed["train_losses"]  # continued, did not crash


def test_retention_prunes_oldest_verified_only(in_tmp):
    root = "ck"
    dirs = [_mk_step(root, n) for n in (1, 2, 3, 4)]
    pending = os.path.join(root, "step_5")  # manifest-less: in flight
    os.makedirs(os.path.join(pending, "state"))
    with open(os.path.join(pending, "state", "data.bin"), "wb") as f:
        f.write(b"y" * 64)

    assert ckpt.prune_checkpoints(root, keep=0) == []  # disabled
    deleted = ckpt.prune_checkpoints(root, keep=2)
    assert deleted == [os.path.abspath(d) for d in dirs[:2]]
    assert not os.path.exists(dirs[0]) and not os.path.exists(dirs[1])
    assert os.path.exists(dirs[2]) and os.path.exists(dirs[3])
    assert os.path.exists(pending)  # never touch unverified dirs
    # idempotent at the floor; the newest good dir always survives
    assert ckpt.prune_checkpoints(root, keep=2) == []
    # the manifest-less dir with non-empty state/ reads as legacy-complete
    # (pre-manifest saves stay restorable); restore_latest's deep verify +
    # fallback is the safety net if it is actually torn
    assert ckpt.latest_step_dir(root) == os.path.abspath(pending)


def test_keep_ckpts_knob_prunes_during_training(in_tmp):
    mc = LLMConfig(**TINY)
    train(mc, _tc(max_iters=8, file_name="kept", ckpt_interval=2,
                  keep_ckpts=2), log=lambda s: None)
    root = os.path.join("checkpoints", "kept")
    steps = sorted(int(d[5:]) for d in os.listdir(root)
                   if d.startswith("step_"))
    assert len(steps) == 2, steps
    assert ckpt.latest_step_dir(root) is not None


def test_rung_down_ladder():
    assert [rung_down(n) for n in (2, 3, 4, 5, 6, 8, 9)] == \
        [1, 2, 2, 4, 4, 4, 8]
    with pytest.raises(AssertionError):
        rung_down(1)
    # the supervisor's fs-only mirror must agree (it avoids importing
    # jax, so the function is duplicated — this pin keeps them honest)
    for n in range(2, 33):
        assert sup._rung_down(n) == rung_down(n)


# ---------------------------------------------------------------------------
# SIGINT graceful stop (satellite): Ctrl-C == SIGTERM path.
# ---------------------------------------------------------------------------

def test_sigint_checkpoints_and_resumes(in_tmp):
    mc = LLMConfig(**TINY)
    quiet = lambda s: None
    full = train(mc, _tc(max_iters=8, file_name="intfull"), log=quiet)

    fired = []

    def log_and_interrupt(s):
        if "iter" in s and not fired:
            fired.append(1)
            os.kill(os.getpid(), signal.SIGINT)

    interrupted = train(mc, _tc(max_iters=8, file_name="intrun",
                                log_interval=1), log=log_and_interrupt)
    assert fired
    assert len(interrupted["train_losses"]) < 9, "SIGINT did not stop"
    assert ckpt.latest_step_dir(os.path.join("checkpoints", "intrun"))

    resumed = train(mc, _tc(max_iters=8, file_name="intrun", resume=True),
                    log=quiet)
    assert resumed["train_losses"] == \
        full["train_losses"][-len(resumed["train_losses"]):]


# ---------------------------------------------------------------------------
# Supervisor state machine (stub workers — no jax in the gang).
# ---------------------------------------------------------------------------

# Stub worker: heartbeats per the supervisor env contract, exits 0 once
# the control file appears. argv: <mode>, mode 'freeze' beats once then
# hangs silently (a SIGSTOP-shaped failure the heartbeat watch must
# catch); 'ok' behaves.
_STUB = textwrap.dedent("""
    import json, os, sys, time
    hb = os.environ.get("SUPERVISOR_HB_FILE", "")
    interval = float(os.environ.get("SUPERVISOR_HB_INTERVAL_S", "0.1"))
    mode = sys.argv[1]
    stop_file = sys.argv[2]
    def beat(seq):
        tmp = hb + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"pid": os.getpid(), "seq": seq}, f)
        os.replace(tmp, hb)
    seq = 0
    while True:
        if hb and (mode != "freeze" or seq == 0):
            beat(seq)
        seq += 1
        if mode != "freeze" and os.path.exists(stop_file):
            sys.exit(0)
        time.sleep(interval)
""")


def _sup_cfg(tmp_path, hosts, **kw):
    base = dict(hosts=hosts, run_name="elastic", poll_s=0.02,
                hb_timeout_s=60.0, max_restarts=4, backoff_base_s=0.05,
                backoff_cap_s=0.1, remesh_deadline_s=0.4,
                hb_interval_s=0.05)
    base.update(kw)
    return sup.SupervisorConfig(**base)


def _run_supervisor(cfg, worker_cmd, timeout=30.0):
    """Run Supervisor.run() on a thread; returns (rc_getter, thread,
    supervisor)."""
    s = sup.Supervisor(cfg, worker_cmd=worker_cmd, log=lambda m: None)
    rc = {}

    def go():
        rc["code"] = s.run()

    t = threading.Thread(target=go, daemon=True)
    t.start()
    _STARTED.append((s, t))
    return rc, t, s


def _wait(predicate, timeout=20.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.02)
    raise TimeoutError(f"timed out waiting for {msg}")


def _state(run_dir):
    try:
        with open(os.path.join(run_dir, sup.STATE_FILE)) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _events(run_dir):
    """The timeline's whole lines so far: a line the supervisor is still
    writing ends the list, it does not empty it (a reader that saw an event
    a moment ago must see it again)."""
    events = []
    try:
        with open(os.path.join(run_dir, sup.TIMELINE_FILE)) as f:
            for line in f:
                if line.strip():
                    events.append(json.loads(line))
    except (OSError, ValueError):
        pass
    return events


@pytest.fixture()
def stub(tmp_path):
    path = tmp_path / "stub_worker.py"
    path.write_text(_STUB)
    return str(path)


def test_supervisor_kill_then_gang_rejoin(in_tmp, stub):
    stop_file = os.path.join(str(in_tmp), "stop_ok")
    cfg = _sup_cfg(in_tmp, hosts=2)
    cmd = lambda slot, n, resume: [sys.executable, stub, "ok", stop_file]
    rc, t, s = _run_supervisor(cfg, cmd)
    run_dir = os.path.join("runs", "elastic")

    _wait(lambda: (_state(run_dir) or {}).get("status") == "running",
          msg="gang 1 up")
    st = _state(run_dir)
    assert st["n_hosts"] == 2 and len(st["workers"]) == 2
    victim = max(st["workers"], key=lambda w: w["slot"])
    os.kill(victim["os_pid"], signal.SIGKILL)

    # the victim keeps its slot (process id) in the restarted gang
    _wait(lambda: (_state(run_dir) or {}).get("generation", 1) >= 2
          and (_state(run_dir) or {}).get("status") == "running",
          msg="gang restart")
    st2 = _state(run_dir)
    assert {w["slot"] for w in st2["workers"]} == {0, 1}
    assert st2["n_hosts"] == 2  # same mesh: a restart, not a re-mesh

    open(stop_file, "w").close()
    t.join(timeout=20)
    assert not t.is_alive() and rc["code"] == sup.EXIT_OK
    names = [e["event"] for e in _events(run_dir)]
    assert "worker_down" in names and "gang_restart" in names \
        and "completed" in names
    down = next(e for e in _events(run_dir) if e["event"] == "worker_down")
    assert down["slot"] == victim["slot"] and down["reason"] == "exit_-9"


def test_supervisor_heartbeat_timeout_detection(in_tmp, stub):
    stop_file = os.path.join(str(in_tmp), "stop_ok")
    cfg = _sup_cfg(in_tmp, hosts=2, hb_timeout_s=0.5)
    # first incarnation (resume=False): slot 1 freezes after one beat —
    # alive for poll() but heartbeat-silent; later incarnations behave
    cmd = lambda slot, n, resume: [
        sys.executable, stub,
        "freeze" if (slot == 1 and not resume) else "ok", stop_file]
    rc, t, s = _run_supervisor(cfg, cmd)
    run_dir = os.path.join("runs", "elastic")

    _wait(lambda: any(e.get("reason") == "heartbeat_timeout"
                      for e in _events(run_dir)),
          msg="heartbeat timeout detection")
    open(stop_file, "w").close()
    t.join(timeout=20)
    assert not t.is_alive() and rc["code"] == sup.EXIT_OK
    down = next(e for e in _events(run_dir)
                if e.get("reason") == "heartbeat_timeout")
    assert down["slot"] == 1


def test_supervisor_held_host_remeshes_rung_down(in_tmp, stub):
    stop_file = os.path.join(str(in_tmp), "stop_ok")
    cfg = _sup_cfg(in_tmp, hosts=2)
    cmd = lambda slot, n, resume: [sys.executable, stub, "ok", stop_file]
    rc, t, s = _run_supervisor(cfg, cmd)
    run_dir = os.path.join("runs", "elastic")

    _wait(lambda: (_state(run_dir) or {}).get("status") == "running",
          msg="gang 1 up")
    st = _state(run_dir)
    victim = max(st["workers"], key=lambda w: w["slot"])
    # hold first (the host is NOT coming back), then SIGKILL
    with open(os.path.join(run_dir, f"hold_{victim['slot']}"), "w") as f:
        f.write("dead host\n")
    os.kill(victim["os_pid"], signal.SIGKILL)

    _wait(lambda: any(e["event"] == "remesh" for e in _events(run_dir)),
          msg="rung-down re-mesh")
    remesh = next(e for e in _events(run_dir) if e["event"] == "remesh")
    assert remesh["old_n"] == 2 and remesh["new_n"] == 1 == rung_down(2)

    _wait(lambda: (_state(run_dir) or {}).get("n_hosts") == 1
          and (_state(run_dir) or {}).get("status") == "running",
          msg="survivor gang up")
    open(stop_file, "w").close()
    t.join(timeout=20)
    assert not t.is_alive() and rc["code"] == sup.EXIT_OK
    assert (_state(run_dir) or {}).get("n_hosts") == 1
    # hold markers are cleared with the old topology
    assert not os.path.exists(os.path.join(run_dir,
                                           f"hold_{victim['slot']}"))


def test_supervisor_single_host_held_is_unrecoverable(in_tmp, stub):
    stop_file = os.path.join(str(in_tmp), "stop_never")
    cfg = _sup_cfg(in_tmp, hosts=1, remesh_deadline_s=0.2)
    cmd = lambda slot, n, resume: [sys.executable, stub, "ok", stop_file]
    rc, t, s = _run_supervisor(cfg, cmd)
    run_dir = os.path.join("runs", "elastic")

    _wait(lambda: (_state(run_dir) or {}).get("status") == "running",
          msg="gang up")
    st = _state(run_dir)
    with open(os.path.join(run_dir, "hold_0"), "w") as f:
        f.write("dead\n")
    os.kill(st["workers"][0]["os_pid"], signal.SIGKILL)
    t.join(timeout=20)
    assert not t.is_alive() and rc["code"] == sup.EXIT_NO_RUNG
    assert (_state(run_dir) or {}).get("status") == "failed"
