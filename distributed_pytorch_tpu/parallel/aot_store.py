"""AOT-compiled program store: zero-cold-start spin-up (ISSUE 18).

Autoscaling and elastic re-mesh are only as fast as the slowest XLA
compile: a fresh serving replica or a rung-down training gang pays full
JIT compilation before emitting a token or taking a step. The engine
already enumerates its complete compiled-program universe statically
(`engine/decode.py::enumerate_trace_signatures`), so the set to
precompile is known in closed form — this module makes each program a
content-addressed on-disk artifact:

* ``<key>.bin``  — pickled ``jax.experimental.serialize_executable``
  triple ``(payload, in_tree, out_tree)``; deserializing yields a ready
  ``Compiled`` with NO trace (TraceGuard counts stay 0 on a full-hit
  spin-up — the acceptance criterion).
* ``<key>.json`` — the manifest: program family, the flattened aval
  fingerprint, the config/geometry env, knob snapshot, runtime versions,
  origin (``warm`` = built by a warming CLI, ``runtime`` = written back
  on a live miss) and the measured compile cost.

The key is a blake2b digest over canonical JSON of everything that can
change the program: family, aval shapes/dtypes/shardings + treedef,
the caller-supplied env (model config, engine geometry or train config,
mesh axes, recipe), the PROGRAM_KNOBS snapshot, and the runtime
fingerprint (jax/jaxlib versions, backend platform + version, device
kind/count, process count). A mismatch in ANY component is a different
key — a version or mesh change can only ever miss, never load a wrong
program.

``AOTStore.build`` is the one entry point integrations use: key ->
load (corrupt entries count ``load_errors`` and fall through) -> on
hit return the deserialized executable; on miss honor AOT_STRICT
(require raises, warn logs), then ``jitted.lower(*avals).compile()``
(the trace fires here, so retrace guards see exactly the cold-start
behavior), write back, return. Hit/miss/compile_ms counters feed
/metrics via the serve scheduler and the spin-up phase records feed
obs/replay's time-to-first-token split.

CLI (also the supervisor's re-mesh pre-warm hook)::

    python -m distributed_pytorch_tpu.parallel.aot_store \
        --store DIR --warm-train --hosts 1 -- <train argv>
    python -m distributed_pytorch_tpu.parallel.aot_store \
        --store DIR --crosscheck --stats
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import hashlib
import json
import logging
import os
import pickle
import sys
import time
from typing import Any, Optional

import jax

from distributed_pytorch_tpu import config

log = logging.getLogger("aot_store")

DEFAULT_DIR = os.path.join("runs", "aot_store")

#: knobs that parameterize compiled programs (kernel tile sizes, quant
#: and overlap gates, speculative K, tier gates, fault injection) — the
#: key material's knob snapshot. Deliberately EXCLUDES per-worker /
#: per-process env (SUPERVISOR_HB_FILE, coordinator addresses): those
#: never change the traced program and would break cross-process key
#: stability.
PROGRAM_KNOBS = (
    "GMM_BLOCK_M", "GMM_BLOCK_N", "GMM_BLOCK_K", "FLASH_DECODE_BLOCK",
    "FLASH_DECODE", "OVERLAP", "OVERLAP_RING",
    "QUANT_KV", "QUANT_W", "SPEC_DECODE", "SPEC_K", "KV_HOST_TIER",
    "KV_HOST_BLOCKS", "TRAIN_POISON_IT",
)


class AOTMissError(RuntimeError):
    """AOT_STRICT=require and the store has no program for this key."""


def _canon(obj: Any) -> str:
    return json.dumps(obj, sort_keys=True, separators=(",", ":"),
                      default=str)


def knob_fingerprint() -> dict:
    """The PROGRAM_KNOBS snapshot as stable strings."""
    return {k: str(config.knob(k)) for k in PROGRAM_KNOBS}


def runtime_fingerprint() -> dict:
    """Everything about the process that can invalidate a serialized
    executable: jax/jaxlib versions, backend platform + its version
    (libtpu on TPU), device kind, and the device/process topology."""
    import jaxlib
    dev = jax.devices()[0]
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "platform_version": str(getattr(dev.client, "platform_version",
                                        "")),
        "device_kind": str(getattr(dev, "device_kind", "")),
        "n_devices": jax.device_count(),
        "n_processes": jax.process_count(),
    }


def _sharding_repr(s) -> Any:
    """Stable description of an aval's sharding constraint (NamedSharding
    renders as spec + mesh axis sizes — never device ids, which differ
    across otherwise-identical processes)."""
    if s is None:
        return None
    mesh = getattr(s, "mesh", None)
    if mesh is not None:
        return {"spec": str(getattr(s, "spec", "")),
                "mesh": dict(zip(mesh.axis_names,
                                 [int(x) for x in mesh.devices.shape]))}
    return str(s)


def aval_fingerprint(avals) -> list:
    """Flattened (path, shape, dtype, sharding) list + the treedef
    string — the shape-signature half of a program key. Path strings
    (not pickled PyTreeDefs) keep the fingerprint identical across
    processes."""
    flat = jax.tree_util.tree_flatten_with_path(avals)
    out = []
    for path, leaf in flat[0]:
        out.append([jax.tree_util.keystr(path),
                    [int(d) for d in leaf.shape], str(leaf.dtype),
                    _sharding_repr(getattr(leaf, "sharding", None))])
    out.append(["__treedef__", str(flat[1])])
    return out


@contextlib.contextmanager
def _no_persistent_cache():
    """Compile past XLA's persistent compilation cache. A store entry has
    to be a SELF-CONTAINED executable: one the persistent cache handed
    back (itself a deserialized executable) can serialize into a blob that
    loads and then cannot find its own functions when it runs (XLA:CPU:
    "Function bitcast_gather_fusion.2 not found" — seen whenever another
    process had put the same program in the cache first). A store miss is
    rare by design, so it always pays for a real compile. The flag alone
    is not enough: jax latches "cache in use" on first check, hence
    reset_cache() on both sides."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = bool(jax.config.jax_enable_compilation_cache)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _serialize(compiled) -> bytes:
    """One blob: the executable, its pytrees, and the ids of the devices
    it was compiled for. jax 0.9.0's `deserialize_and_load` loads onto
    EVERY device of the backend unless told otherwise, and a one-device
    program loaded that way rejects its inputs ("expected 8 shards")."""
    from jax.experimental import serialize_executable as se
    payload, in_tree, out_tree = se.serialize(compiled)
    ids = [d.id for d in compiled.runtime_executable().local_devices()]
    return pickle.dumps((payload, in_tree, out_tree, ids))


def _deserialize(blob: bytes):
    from jax.experimental import serialize_executable as se
    payload, in_tree, out_tree, ids = pickle.loads(blob)
    by_id = {d.id: d for d in jax.devices()}
    return se.deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in ids])


class AOTStore:
    """Content-addressed on-disk store of serialized XLA executables.

    One instance per process/replica; counters are lifetime. `_runtime`
    overrides the process runtime fingerprint — tests use it to prove a
    version skew can only miss.
    """

    def __init__(self, root: str, *, strict: Optional[str] = None,
                 _runtime: Optional[dict] = None):
        self.root = root
        self.strict = strict if strict else config.knob("AOT_STRICT")
        self._runtime = _runtime
        os.makedirs(root, exist_ok=True)
        self.hits = 0
        self.misses = 0
        self.saves = 0
        self.load_errors = 0
        self.fallbacks = 0            # loaded program rejected its inputs
        self.compile_ms = 0.0
        self.load_ms = 0.0
        #: per-program spin-up phase records ({family, phase, ms, key})
        #: — the obs/replay TTFT-split source (serve dumps them to
        #: runs/serve/spinup.jsonl)
        self.events: list = []

    # -- keying -----------------------------------------------------------

    def key(self, family: str, avals, env: dict) -> str:
        material = {
            "family": family,
            "avals": aval_fingerprint(avals),
            "env": env,
            "knobs": knob_fingerprint(),
            "runtime": self._runtime or runtime_fingerprint(),
        }
        h = hashlib.blake2b(_canon(material).encode(),
                            digest_size=16).hexdigest()
        return f"{family}-{h}"

    def _paths(self, key: str) -> tuple:
        return (os.path.join(self.root, key + ".bin"),
                os.path.join(self.root, key + ".json"))

    # -- load / save ------------------------------------------------------

    def load(self, key: str):
        """Deserialize the stored executable for `key`, or None (absent
        OR unreadable — a corrupt entry counts `load_errors`, is logged,
        and the caller compiles as on any miss; a wrong program is
        impossible by keying, so the only failure mode is a miss)."""
        bin_path, man_path = self._paths(key)
        if not (os.path.exists(bin_path) and os.path.exists(man_path)):
            return None
        try:
            with open(bin_path, "rb") as f:
                return _deserialize(f.read())
        except Exception as e:  # corrupt/incompatible blob -> a miss
            self.load_errors += 1
            log.warning("[aot] unreadable entry %s (%s: %s) — counted as "
                        "a miss", key, type(e).__name__, e)
            return None

    def save(self, key: str, compiled, manifest: dict) -> bool:
        """Serialize, VERIFY the round-trip, and write atomically (tmp +
        rename: a torn write can never be loaded as a valid entry). The
        verify matters: an executable handed back by XLA's persistent
        compilation cache can serialize into a blob that fails to
        re-link its symbols — writing it would poison the store for
        every future replica, so an unloadable blob is rejected here
        (build() then retries the compile with that cache bypassed)."""
        try:
            blob = _serialize(compiled)
            _deserialize(blob)
        except Exception as e:  # unserializable backend — store disabled
            log.warning("[aot] cannot serialize %s (%s: %s)", key,
                        type(e).__name__, e)
            return False
        bin_path, man_path = self._paths(key)
        for path, data, mode in ((bin_path, blob, "wb"),
                                 (man_path, json.dumps(
                                     manifest, indent=1, sort_keys=True,
                                     default=str), "w")):
            tmp = path + f".tmp.{os.getpid()}"
            with open(tmp, mode) as f:
                f.write(data)
            os.replace(tmp, path)
        self.saves += 1
        return True

    # -- the one integration entry point ----------------------------------

    def build(self, family: str, jitted, avals, env: dict, *,
              origin: str = "runtime"):
        """Load-or-compile one program: the executable for `key(family,
        avals, env)` on hit (no trace), else — per AOT_STRICT —
        ``jitted.lower(*avals).compile()`` (traces exactly like a cold
        start) followed by write-back."""
        key = self.key(family, avals, env)
        t0 = time.perf_counter()
        fn = self.load(key)
        if fn is not None:
            ms = (time.perf_counter() - t0) * 1e3
            self.hits += 1
            self.load_ms += ms
            self.events.append({"family": family, "phase": "load",
                                "ms": round(ms, 3), "key": key})
            return fn
        self.misses += 1
        if self.strict == "require":
            raise AOTMissError(
                f"AOT_STRICT=require: no stored program for {family} "
                f"({key}) in {self.root}")
        if self.strict == "warn":
            log.warning("[aot] miss: compiling %s (%s)", family, key)
        t0 = time.perf_counter()
        with _no_persistent_cache():
            compiled = jitted.lower(*avals).compile()
            ms = (time.perf_counter() - t0) * 1e3
            manifest = {
                "key": key, "family": family, "origin": origin, "env": env,
                "avals": aval_fingerprint(avals),
                "knobs": knob_fingerprint(),
                "runtime": self._runtime or runtime_fingerprint(),
                "compile_ms": round(ms, 3),
            }
            if not self.save(key, compiled, manifest):
                # save() rejects a blob that fails its serialize
                # round-trip. One retry past the in-memory compilation
                # memo too (clear_caches), which can hand back the same
                # executable again.
                t1 = time.perf_counter()
                jax.clear_caches()
                compiled = jitted.lower(*avals).compile()
                ms += (time.perf_counter() - t1) * 1e3
                manifest["compile_ms"] = round(ms, 3)
                self.save(key, compiled, manifest)
        self.compile_ms += ms
        self.events.append({"family": family, "phase": "compile",
                            "ms": round(ms, 3), "key": key})
        return compiled

    # -- introspection ----------------------------------------------------

    def manifests(self) -> dict:
        """key -> manifest dict for every readable entry on disk."""
        out = {}
        for name in sorted(os.listdir(self.root)):
            if not name.endswith(".json"):
                continue
            try:
                with open(os.path.join(self.root, name)) as f:
                    m = json.load(f)
                out[m["key"]] = m
            except Exception:  # torn manifest — load() would miss it too
                continue
        return out

    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "saves": self.saves, "load_errors": self.load_errors,
                "fallbacks": self.fallbacks,
                "compile_ms": round(self.compile_ms, 3),
                "load_ms": round(self.load_ms, 3),
                "entries": len(self.manifests()), "root": self.root}


class AOTInputMismatch(RuntimeError):
    """AOT_STRICT=require: a stored program rejected its live inputs."""


class SafeCompiled:
    """A store-built executable beside the jitted fn it was built from.
    A ``Compiled`` rejects inputs whose layout/sharding drifted from the
    stored avals (it cannot re-trace). That is a store bug, not a slow
    start: under AOT_STRICT=require the rejection is raised as
    `AOTInputMismatch`; otherwise it is LOGGED and COUNTED — the load no
    longer counts as a hit, `misses` and `fallbacks` go up — and the call
    (and every later one) goes to the jitted fn, whose trace count
    exposes the reroute to the CI parity checks."""

    def __init__(self, compiled, jitted, store: AOTStore, family: str):
        self._compiled = compiled
        self._jitted = jitted
        self._store = store
        self._family = family
        self._broken = False

    def __call__(self, *args):
        if not self._broken:
            try:
                return self._compiled(*args)
            except Exception as e:
                if self._store.strict == "require":
                    raise AOTInputMismatch(
                        f"AOT_STRICT=require: stored {self._family} "
                        f"rejected its live inputs ({type(e).__name__}: "
                        f"{e})") from e
                self._broken = True
                self._store.fallbacks += 1
                self._store.misses += 1
                self._store.hits = max(0, self._store.hits - 1)
                log.warning("[aot] stored %s rejected live inputs (%s: "
                            "%s) — counted as a miss, compiling",
                            self._family, type(e).__name__, e)
        return self._jitted(*args)


def resolve_store(dir_: Optional[str] = None,
                  enable: Optional[bool] = None,
                  strict: Optional[str] = None) -> Optional[AOTStore]:
    """Knob-level store resolution (the quant-gate resolve shape):
    AOT_STORE on|off overrides, auto = on iff a dir is configured; an
    explicit `enable`/`dir_` from a constructor/CLI wins over knobs."""
    mode = config.knob("AOT_STORE")
    if enable is not None:
        mode = "on" if enable else "off"
    root = dir_ or config.knob("AOT_STORE_DIR")
    if mode == "off" or (mode == "auto" and not root):
        return None
    return AOTStore(root or DEFAULT_DIR, strict=strict)


def store_configured() -> bool:
    """Jax-free knob check (the supervisor gates its pre-warm subprocess
    on this without importing a backend — keep this module unimported
    there; the logic mirrors resolve_store)."""
    mode = config.knob("AOT_STORE")
    return mode == "on" or (mode == "auto"
                            and bool(config.knob("AOT_STORE_DIR")))


# ---------------------------------------------------------------------------
# Cross-check: manifest key set vs the engine's static program universe.
# ---------------------------------------------------------------------------

def crosscheck(store: AOTStore) -> list:
    """Errors if the store's WARM manifest set diverges from
    `enumerate_trace_signatures` for any engine geometry it claims to
    cover — an uncovered signature (the warming walk skipped a program
    the engine will request) or a stale key (a warm entry the engine can
    never request) both fail. Runtime-origin write-backs are checked
    only for requestability: the admit bucket clip
    (min(pow2, max_len - prefix_len)) legitimately produces
    non-enumerated block-multiple buckets on prefix hits."""
    from distributed_pytorch_tpu.engine.decode import \
        enumerate_trace_signatures
    errors: list = []
    groups: dict = {}
    for key, m in store.manifests().items():
        env = m.get("env", {})
        if env.get("kind") != "engine":
            continue  # train_step etc: no closed-form enumeration
        g = env.get("geometry", {})
        gk = _canon(g)
        groups.setdefault(gk, {"geometry": g, "entries": []})
        groups[gk]["entries"].append(m)
    for grp in groups.values():
        g = grp["geometry"]
        try:
            sig = enumerate_trace_signatures(
                min_bucket=int(g["min_bucket"]),
                block_size=int(g["block_size"]),
                max_len=int(g["max_len"]),
                prefill_chunk=int(g["prefill_chunk"]),
                spec_k=int(g.get("spec_k", 0)))
        except Exception as e:
            errors.append(f"unreadable geometry {g}: {e}")
            continue
        expected = {"step": sig["step"], "fused_step": sig["fused_step"],
                    "spec_step": sig["spec_step"],
                    "promote": sig["promote"] if g.get("host_tier") else 0}
        gname = (f"slots={g.get('n_slots')} max_len={g.get('max_len')} "
                 f"chunk={g.get('prefill_chunk')}")
        warm = [m for m in grp["entries"] if m.get("origin") == "warm"]
        warm_buckets = sorted(int(m["env"].get("bucket"))
                              for m in warm if m["family"] == "admit")
        if warm:
            # coverage: every statically-enumerated signature present
            for fam, want in expected.items():
                got = sum(1 for m in warm if m["family"] == fam)
                if got != want:
                    errors.append(
                        f"[{gname}] family {fam}: {got} warm entr(ies), "
                        f"enumeration expects {want}")
            if warm_buckets != sorted(sig["buckets"]):
                errors.append(
                    f"[{gname}] admit buckets {warm_buckets} != "
                    f"enumerated {sorted(sig['buckets'])}")
        # requestability: no entry the engine could never ask for
        for m in grp["entries"]:
            fam = m["family"]
            if fam not in ("step", "fused_step", "admit", "spec_step",
                           "promote"):
                errors.append(f"[{gname}] unknown family {fam}")
                continue
            if fam in expected and expected[fam] == 0:
                errors.append(f"[{gname}] stale key: {fam} entry but the "
                              "engine geometry never requests it")
            if fam == "admit":
                b = int(m["env"].get("bucket", -1))
                bs, ml = int(g["block_size"]), int(g["max_len"])
                if b <= 0 or b % bs or b > ml:
                    errors.append(f"[{gname}] stale key: admit bucket {b} "
                                  f"not requestable (block {bs}, "
                                  f"max_len {ml})")
    return errors


# ---------------------------------------------------------------------------
# Train-step warming (the supervisor's re-mesh pre-warm target).
# ---------------------------------------------------------------------------

def train_step_env(model_cfg, train_cfg, mesh) -> dict:
    """Key env for the train step: the FULL configs (train_cfg.seed is
    baked into the compiled program via fold_in; poison-iteration and
    kernel knobs ride the knob snapshot) + mesh axis sizes."""
    return {"kind": "train",
            "model_cfg": dataclasses.asdict(model_cfg),
            "train_cfg": dataclasses.asdict(train_cfg),
            "mesh": dict(zip(mesh.axis_names,
                             [int(x) for x in mesh.devices.shape]))}


def train_step_avals(state, model_cfg, train_cfg, mesh, *,
                     grad_accum: int, b_glob: int) -> tuple:
    """(state, x, y) avals exactly as the train loop calls its step:
    state avals carry the committed leaves' shardings, batches the
    loader's pspec — key equality between a pre-warm process and the
    restarted worker holds by construction."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from distributed_pytorch_tpu.parallel import sharding as shd
    sds = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                       sharding=getattr(l, "sharding",
                                                        None)), state)
    bsh = NamedSharding(mesh, shd.batch_pspec(train_cfg.parallelism, mesh,
                                              leading_accum=True))
    batch = jax.ShapeDtypeStruct((grad_accum, b_glob,
                                  model_cfg.block_size), jnp.int32,
                                 sharding=bsh)
    return (sds, batch, batch)


def wrap_train_step(store: Optional[AOTStore], train_step, state,
                    model_cfg, train_cfg, mesh, *, grad_accum: int,
                    b_glob: int, origin: str = "runtime"):
    """AOT-back the train loop's step fn (train/loop.py): hit =
    deserialized executable (no trace, restart-to-first-step is weight
    load), miss = eager lower+compile+write-back (vs the JIT path's
    first-call compile). GuardedFn delegates `.lower`, so the retrace
    guard counts a miss's compile exactly like the JIT path; the guard
    is re-attached so loop-side `expect(0)` regions keep working."""
    if store is None:
        return train_step
    from distributed_pytorch_tpu.obs.retrace import guarded
    avals = train_step_avals(state, model_cfg, train_cfg, mesh,
                             grad_accum=grad_accum, b_glob=b_glob)
    compiled = store.build("train_step", train_step, avals,
                           train_step_env(model_cfg, train_cfg, mesh),
                           origin=origin)
    safe = SafeCompiled(compiled, train_step, store, "train_step")
    return guarded(safe, train_step.trace_guard)


def warm_train(store: AOTStore, train_argv: list, *,
               origin: str = "warm") -> dict:
    """Compile-and-store the train step for one single-process config,
    mirroring the loop preamble (mesh_for -> create_train_state ->
    make_train_step) so the produced key equals the worker's. Multi-host
    gangs compile against a different process topology (n_processes is
    deliberately key material: a single-process executable must never
    load into a gang member) — callers skip hosts > 1."""
    from distributed_pytorch_tpu.__main__ import parse_train_argv
    from distributed_pytorch_tpu.parallel.mesh import mesh_for
    from distributed_pytorch_tpu.train.state import create_train_state
    from distributed_pytorch_tpu.train.step import make_train_step
    model_cfg, train_cfg = parse_train_argv(train_argv)
    mesh = mesh_for(train_cfg.parallelism, tp_size=train_cfg.tp_size,
                    ep_size=train_cfg.ep_size, sp_size=train_cfg.sp_size,
                    pp_size=train_cfg.pp_size, dp_size=train_cfg.dp_size)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    b_glob = train_cfg.batch_size * sizes["data"]
    grad_accum = train_cfg.total_batch_size // (b_glob
                                                * model_cfg.block_size)
    model, tx, state, state_sharding = create_train_state(
        model_cfg, train_cfg, mesh)
    step = make_train_step(model, tx, model_cfg, train_cfg, mesh,
                           state_sharding)
    wrap_train_step(store, step, state, model_cfg, train_cfg, mesh,
                    grad_accum=grad_accum, b_glob=b_glob, origin=origin)
    return store.stats()


# ---------------------------------------------------------------------------
# CLI.
# ---------------------------------------------------------------------------

def _split_argv(argv):
    argv = list(argv)
    if "--" in argv:
        i = argv.index("--")
        return argv[:i], argv[i + 1:]
    return argv, []


def main(argv: Optional[list] = None) -> int:
    own, train_argv = _split_argv(
        sys.argv[1:] if argv is None else argv)
    ap = argparse.ArgumentParser(
        prog="python -m distributed_pytorch_tpu.parallel.aot_store",
        description="AOT program store maintenance: warm the train step "
                    "for a config (train flags after `--`), cross-check "
                    "manifests against the engine's static program "
                    "enumeration, print stats")
    ap.add_argument("--store", default=None,
                    help="store dir (default: AOT_STORE/AOT_STORE_DIR "
                         "knobs; required if they resolve off)")
    ap.add_argument("--warm-train", action="store_true",
                    help="compile+store the train step for the train "
                         "argv after `--`")
    ap.add_argument("--hosts", type=int, default=1,
                    help="gang size the warm targets; >1 is skipped "
                         "(multi-process program keys are not "
                         "reproducible in one process — by design)")
    ap.add_argument("--cpu-devices", type=int, default=0,
                    help="virtual CPU devices to request before jax "
                         "init (mirror the worker's mesh on CPU)")
    ap.add_argument("--crosscheck", action="store_true",
                    help="verify manifest keys vs "
                         "enumerate_trace_signatures; stale or missing "
                         "coverage exits 1")
    ap.add_argument("--stats", action="store_true",
                    help="print store stats JSON")
    args = ap.parse_args(own)

    if args.cpu_devices > 0:
        from distributed_pytorch_tpu import compat
        compat.request_cpu_devices(args.cpu_devices)

    store = resolve_store(args.store, enable=True if args.store else None)
    if store is None:
        print("aot_store: disabled (AOT_STORE/AOT_STORE_DIR unset and no "
              "--store)", file=sys.stderr)
        return 0

    rc = 0
    if args.warm_train:
        if args.hosts > 1:
            print(f"aot_store: skip warm-train for hosts={args.hosts} "
                  "(multi-process keys not reproducible in-process)")
        elif not train_argv:
            print("aot_store: --warm-train needs train flags after `--`",
                  file=sys.stderr)
            rc = 2
        else:
            t0 = time.perf_counter()
            stats = warm_train(store, train_argv)
            print(f"aot_store: warm-train done in "
                  f"{time.perf_counter() - t0:.1f}s "
                  f"hits={stats['hits']} misses={stats['misses']}")
    if args.crosscheck:
        errors = crosscheck(store)
        for e in errors:
            print(f"aot_store crosscheck: {e}", file=sys.stderr)
        print(f"aot_store crosscheck: {len(store.manifests())} entr(ies)"
              f", {len(errors)} error(s)")
        if errors:
            rc = 1
    if args.stats or not (args.warm_train or args.crosscheck):
        print(json.dumps(store.stats(), indent=1, sort_keys=True))
    return rc


if __name__ == "__main__":
    sys.exit(main())
