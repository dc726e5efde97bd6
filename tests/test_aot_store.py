"""AOT program store (parallel/aot_store.py, ISSUE 18): content-
addressed executables keyed by (family, shape signature, knobs,
jax/backend runtime, topology). The contracts under test: keys are
stable across processes (the pre-warm CLI's whole value), any version /
mesh / knob skew can only MISS (a wrong-program load is impossible by
keying), a corrupt entry degrades to JIT with a counter instead of
crashing, a warmed engine decodes bit-identically to a cold one with
zero JIT traces and a 1.0 hit rate, AOT_STRICT=require turns a miss
into a hard error, the supervisor runs its pre-warm hook on re-mesh,
and the manifest cross-check catches both uncovered signatures and
stale keys."""

import json
import os
import shutil
import signal
import subprocess
import sys
import textwrap
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.models.gpt import LLM
from distributed_pytorch_tpu.parallel import aot_store
from distributed_pytorch_tpu.parallel.aot_store import (AOTMissError,
                                                        AOTStore)
from distributed_pytorch_tpu.train import supervisor as sup

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Keying.
# ---------------------------------------------------------------------------

_KEY_SCRIPT = textwrap.dedent("""
    import sys
    sys.path.insert(0, %r)
    import jax
    import jax.numpy as jnp
    from distributed_pytorch_tpu.parallel.aot_store import AOTStore
    s = AOTStore(sys.argv[1])
    avals = ({"w": jax.ShapeDtypeStruct((4, 8), jnp.float32)},
             jax.ShapeDtypeStruct((2,), jnp.int32))
    print(s.key("step", avals, {"kind": "engine", "n_slots": 2}))
""") % REPO


def test_key_stable_across_processes(tmp_path):
    """Two separate interpreters derive the SAME key for the same
    (family, avals, env) — pre-warming in one process and loading in
    another works only because nothing process-local (device ids,
    pickled treedefs, dict order) leaks into the hash."""
    keys = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _KEY_SCRIPT, str(tmp_path)],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr
        keys.append(out.stdout.strip())
    assert keys[0] == keys[1]
    assert keys[0].startswith("step-")


def _trivial():
    jitted = jax.jit(lambda x: x + 1)
    avals = [jax.ShapeDtypeStruct((4,), jnp.float32)]
    return jitted, avals


def test_any_skew_changes_the_key(tmp_path):
    """Version, topology, mesh-shape, knob, shape, and family skews each
    produce a DIFFERENT key — the only cross-version/config failure mode
    is a miss, never a wrong-program load."""
    rt = {"jax": "0.4.37", "jaxlib": "0.4.36", "backend": "cpu",
          "platform_version": "", "device_kind": "cpu",
          "n_devices": 1, "n_processes": 1}
    s = AOTStore(str(tmp_path), _runtime=rt)
    _, avals = _trivial()
    base = s.key("step", avals, {"kind": "engine"})
    skews = [
        AOTStore(str(tmp_path),
                 _runtime={**rt, "jaxlib": "0.4.35"}),       # version
        AOTStore(str(tmp_path),
                 _runtime={**rt, "n_processes": 2}),         # topology
        AOTStore(str(tmp_path),
                 _runtime={**rt, "device_kind": "TPU v4"}),  # silicon
    ]
    for other in skews:
        assert other.key("step", avals, {"kind": "engine"}) != base
    # env (mesh/geometry), shape, and family skews on the same runtime
    assert s.key("step", avals,
                 {"kind": "engine", "mesh": {"model": 2}}) != base
    assert s.key("step", [jax.ShapeDtypeStruct((8,), jnp.float32)],
                 {"kind": "engine"}) != base
    assert s.key("fused_step", avals, {"kind": "engine"}) != base


def test_knob_skew_changes_the_key(tmp_path, monkeypatch):
    """PROGRAM_KNOBS are key material: flipping one (here a grouped-matmul
    tile that changes the compiled kernel) re-keys every program."""
    s = AOTStore(str(tmp_path))
    _, avals = _trivial()
    base = s.key("step", avals, {"kind": "engine"})
    monkeypatch.setenv("GMM_BLOCK_M", "256")  # default is 128
    assert s.key("step", avals, {"kind": "engine"}) != base


def test_miss_compiles_and_second_store_hits(tmp_path):
    jitted, avals = _trivial()
    s1 = AOTStore(str(tmp_path))
    fn = s1.build("step", jitted, avals, {"kind": "t"})
    assert (s1.misses, s1.hits, s1.saves) == (1, 0, 1)
    assert fn(jnp.zeros((4,), jnp.float32)).tolist() == [1.0] * 4
    s2 = AOTStore(str(tmp_path))  # fresh handle = fresh counters
    fn2 = s2.build("step", jitted, avals, {"kind": "t"})
    assert (s2.misses, s2.hits) == (0, 1)
    assert s2.compile_ms == 0.0 and s2.load_ms > 0.0
    assert fn2(jnp.ones((4,), jnp.float32)).tolist() == [2.0] * 4
    # a DIFFERENT program never loads from the populated store
    s3 = AOTStore(str(tmp_path))
    s3.build("step", jitted, avals, {"kind": "t", "other": 1})
    assert (s3.misses, s3.hits) == (1, 0)


def test_corrupt_entry_falls_back_to_jit(tmp_path):
    """A torn/garbage .bin must count load_errors and recompile — never
    crash, never return a broken callable."""
    jitted, avals = _trivial()
    s1 = AOTStore(str(tmp_path))
    s1.build("step", jitted, avals, {"kind": "t"})
    [bin_path] = [os.path.join(tmp_path, n) for n in os.listdir(tmp_path)
                  if n.endswith(".bin")]
    with open(bin_path, "wb") as f:
        f.write(b"not a pickled executable")
    s2 = AOTStore(str(tmp_path))
    fn = s2.build("step", jitted, avals, {"kind": "t"})
    assert s2.load_errors == 1 and s2.misses == 1 and s2.hits == 0
    assert fn(jnp.zeros((4,), jnp.float32)).tolist() == [1.0] * 4
    # the recompile rewrote the entry: a third store hits again
    s3 = AOTStore(str(tmp_path))
    s3.build("step", jitted, avals, {"kind": "t"})
    assert (s3.hits, s3.load_errors) == (1, 0)


def test_strict_require_raises_on_miss(tmp_path):
    jitted, avals = _trivial()
    s = AOTStore(str(tmp_path), strict="require")
    with pytest.raises(AOTMissError):
        s.build("step", jitted, avals, {"kind": "t"})
    # ... and is satisfied once another store populated the entry
    AOTStore(str(tmp_path)).build("step", jitted, avals, {"kind": "t"})
    s.build("step", jitted, avals, {"kind": "t"})
    assert s.hits == 1


# ---------------------------------------------------------------------------
# Engine integration: warmed spin-up == cold spin-up, bit for bit.
# ---------------------------------------------------------------------------

def _tiny_cfg():
    return LLMConfig(vocab_size=97, block_size=64, n_embd=48, n_head=4,
                     n_kv_heads=2, attn="gqa", n_layer=2, up_dim=64,
                     non_linearity="swiglu", pos_emb="rope", dropout=0.0)


PROMPTS = [[1, 2, 3], [5, 6, 7, 8, 9, 10, 11], [20] * 17, [42, 43]]


@pytest.fixture(scope="module")
def tiny_model():
    cfg = _tiny_cfg()
    model = LLM(cfg, attn_impl="naive")
    rng = jax.random.PRNGKey(0)
    x = jnp.zeros((1, cfg.block_size), jnp.int32)
    variables = model.init({"params": rng, "dropout": rng}, x, x)
    return model, dict(variables)


@pytest.fixture(scope="module")
def warm_root(tiny_model, tmp_path_factory):
    """A store populated by one engine's warm walk (origin='warm' — the
    aot_warm.py path), shared by the hit-rate/parity/crosscheck tests."""
    model, variables = tiny_model
    root = str(tmp_path_factory.mktemp("aot_warm_store"))
    eng = DecodeEngine(model, variables, n_slots=2, temperature=0.0,
                       min_bucket=8, aot_store=AOTStore(root))
    eng.warm_aot(origin="warm")
    assert eng.aot_store.misses > 0  # it actually compiled the universe
    return root


def test_warmed_engine_bit_identical_zero_traces(tiny_model, warm_root):
    model, variables = tiny_model
    cold = DecodeEngine(model, variables, n_slots=2, temperature=0.0,
                        min_bucket=8, aot_store=False)
    ref = cold.run(PROMPTS, max_new_tokens=6)

    store = AOTStore(warm_root)  # fresh handle: the restarted replica
    warm = DecodeEngine(model, variables, n_slots=2, temperature=0.0,
                        min_bucket=8, aot_store=store)
    warm.warm_aot(origin="runtime")
    out = warm.run(PROMPTS, max_new_tokens=6)

    assert out == ref  # greedy decode is bit-identical warmed vs cold
    # hit rate 1.0: every program came from the store...
    assert store.misses == 0 and store.hits > 0
    assert store.fallbacks == 0 and store.compile_ms == 0.0
    # ...and NOTHING was traced/JIT-compiled in the warmed process
    assert warm.step_traces == 0
    assert warm.fused_step_traces == 0
    assert warm.spec_step_traces == 0 and warm.promote_traces == 0
    assert sum(warm.admit_traces.values()) == 0


def test_crosscheck_clean_then_uncovered_then_stale(warm_root, tmp_path):
    """The commscheck cross-check: the warm manifest set must equal the
    static enumeration — deleting a warm entry (uncovered signature) or
    planting an unrequestable one (stale key) both produce errors."""
    assert aot_store.crosscheck(AOTStore(warm_root)) == []

    # uncovered: drop one warmed admit bucket from a copy of the store
    holey = str(tmp_path / "holey")
    shutil.copytree(warm_root, holey)
    victim = next(k for k, m in AOTStore(holey).manifests().items()
                  if m["family"] == "admit")
    os.remove(os.path.join(holey, victim + ".json"))
    os.remove(os.path.join(holey, victim + ".bin"))
    errs = aot_store.crosscheck(AOTStore(holey))
    assert errs and any("admit" in e for e in errs)

    # stale: an admit entry for a bucket no engine geometry can request
    stale = str(tmp_path / "stale")
    shutil.copytree(warm_root, stale)
    st = AOTStore(stale)
    donor = next(m for m in st.manifests().values()
                 if m["family"] == "admit")
    bogus = dict(donor, key="admit-0000feed",
                 env=dict(donor["env"], bucket=7))  # not block-multiple
    with open(os.path.join(stale, "admit-0000feed.json"), "w") as f:
        json.dump(bogus, f)
    with open(os.path.join(stale, "admit-0000feed.bin"), "wb") as f:
        f.write(b"x")
    errs = aot_store.crosscheck(st)
    assert any("stale key" in e for e in errs)


def test_resolve_store_knob_gate(tmp_path, monkeypatch):
    monkeypatch.delenv("AOT_STORE", raising=False)
    monkeypatch.delenv("AOT_STORE_DIR", raising=False)
    assert aot_store.resolve_store() is None          # auto + no dir
    assert not aot_store.store_configured()
    monkeypatch.setenv("AOT_STORE_DIR", str(tmp_path))
    s = aot_store.resolve_store()                     # auto + dir = on
    assert s is not None and s.root == str(tmp_path)
    assert aot_store.store_configured()
    monkeypatch.setenv("AOT_STORE", "off")            # off wins over dir
    assert aot_store.resolve_store() is None
    assert not aot_store.store_configured()


# ---------------------------------------------------------------------------
# Supervisor re-mesh pre-warm (stub workers + stub pre-warm cmd).
# ---------------------------------------------------------------------------

_STUB = textwrap.dedent("""
    import json, os, sys, time
    hb = os.environ.get("SUPERVISOR_HB_FILE", "")
    interval = float(os.environ.get("SUPERVISOR_HB_INTERVAL_S", "0.1"))
    stop_file = sys.argv[1]
    seq = 0
    while True:
        if hb:
            tmp = hb + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"pid": os.getpid(), "seq": seq}, f)
            os.replace(tmp, hb)
        seq += 1
        if os.path.exists(stop_file):
            sys.exit(0)
        time.sleep(interval)
""")


@pytest.fixture()
def in_tmp(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    return tmp_path


def _events(run_dir):
    try:
        with open(os.path.join(run_dir, sup.TIMELINE_FILE)) as f:
            return [json.loads(line) for line in f if line.strip()]
    except (OSError, ValueError):
        return []


def _wait(predicate, timeout=20.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.02)
    raise TimeoutError(f"timed out waiting for {msg}")


def test_supervisor_prewarms_on_remesh(in_tmp):
    """A held-dead host forces the rung-down re-mesh; the supervisor
    must run prewarm_cmd(new_n) SYNCHRONOUSLY before the survivor gang
    starts and put an `aot_prewarm` record (rc 0, new topology) on the
    timeline. The stub cmd writes a marker instead of compiling."""
    stub = in_tmp / "stub_worker.py"
    stub.write_text(_STUB)
    stop_file = str(in_tmp / "stop_ok")
    marker = str(in_tmp / "prewarmed")
    cfg = sup.SupervisorConfig(
        hosts=2, run_name="aot", poll_s=0.02, hb_timeout_s=60.0,
        max_restarts=4, backoff_base_s=0.05, backoff_cap_s=0.1,
        remesh_deadline_s=0.4, hb_interval_s=0.05)
    prewarm_calls = []

    def prewarm_cmd(n):
        prewarm_calls.append(n)
        return [sys.executable, "-c",
                f"open({marker!r}, 'w').write('{n}')"]

    s = sup.Supervisor(
        cfg, worker_cmd=lambda slot, n, resume: [
            sys.executable, str(stub), stop_file],
        prewarm_cmd=prewarm_cmd, log=lambda m: None)
    rc = {}
    t = threading.Thread(target=lambda: rc.update(code=s.run()),
                         daemon=True)
    t.start()
    run_dir = os.path.join("runs", "aot")

    def state():
        try:
            with open(os.path.join(run_dir, sup.STATE_FILE)) as f:
                return json.load(f)
        except (OSError, ValueError):
            return {}

    _wait(lambda: state().get("status") == "running", msg="gang up")
    victim = max(state()["workers"], key=lambda w: w["slot"])
    with open(os.path.join(run_dir, f"hold_{victim['slot']}"), "w") as f:
        f.write("dead host\n")
    os.kill(victim["os_pid"], signal.SIGKILL)

    _wait(lambda: any(e["event"] == "aot_prewarm"
                      for e in _events(run_dir)), msg="pre-warm event")
    open(stop_file, "w").close()
    t.join(timeout=20)
    assert not t.is_alive() and rc["code"] == sup.EXIT_OK
    ev = next(e for e in _events(run_dir) if e["event"] == "aot_prewarm")
    assert ev["n_hosts"] == 1 and ev["rc"] == 0
    assert prewarm_calls == [1]
    with open(marker) as f:
        assert f.read() == "1"  # the subprocess really ran
    names = [e["event"] for e in _events(run_dir)]
    # ordering: the pre-warm lands with the re-mesh decision, before
    # the survivor gang's restart record
    assert names.index("aot_prewarm") > names.index("remesh")


def test_default_prewarm_cmd_gated_on_knobs(in_tmp, monkeypatch):
    """The built-in pre-warm hook is a no-op unless the store knobs are
    live (a disabled store must cost no subprocess), and shells out to
    the aot_store CLI with the run's own train argv when they are."""
    cfg = sup.SupervisorConfig(hosts=2, run_name="aot", cpu_devices=2,
                               train_argv=["--dataset", "synthetic"])
    s = sup.Supervisor(cfg, worker_cmd=lambda *a: ["true"],
                       log=lambda m: None)
    monkeypatch.delenv("AOT_STORE", raising=False)
    monkeypatch.delenv("AOT_STORE_DIR", raising=False)
    assert s._default_prewarm_cmd(1) is None
    monkeypatch.setenv("AOT_STORE", "off")
    monkeypatch.setenv("AOT_STORE_DIR", str(in_tmp))
    assert s._default_prewarm_cmd(1) is None  # off beats a configured dir
    monkeypatch.setenv("AOT_STORE", "auto")
    cmd = s._default_prewarm_cmd(1)
    assert cmd is not None
    assert "distributed_pytorch_tpu.parallel.aot_store" in cmd
    assert cmd[cmd.index("--hosts") + 1] == "1"
    assert cmd[cmd.index("--cpu-devices") + 1] == "2"
    assert cmd[-2:] == ["--dataset", "synthetic"]
