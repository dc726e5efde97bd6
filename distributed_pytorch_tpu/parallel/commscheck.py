"""Static comms audit (ISSUE 15): device-free collective inventory,
donation and trace-budget verification over the shardcheck matrix.

shardcheck (round 16) proves the PartitionSpec tables are *well-formed*;
this module proves what the programs built from them actually *say*. It
traces the REAL compiled families — `train/step.py`'s train step and the
engine's step / fused chunked-prefill step / bucket admit (module-level
factories in engine/decode.py, so the audited program IS the served
program) — with `jax.eval_shape`-style abstract arguments, then walks the
closed jaxpr recursively (pjit / shard_map / scan / remat / custom-vjp
sub-jaxprs; scan bodies weighted by trip count) and inventories every
EXPLICIT collective primitive (`psum`, `all_gather`, `psum_scatter`,
`ppermute`, `all_to_all`) with its mesh axes and per-device bytes from
the operand avals.

Two layers, because GSPMD-derived collectives never appear in a jaxpr:

* **explicit inventory** — what the trace literally contains: the
  collective-matmul overlap rings (ops/collective_matmul.py), ring/
  Ulysses attention hops over 'seq', shard_map psums. Byte counts are
  per-shard operand bytes x (scan-weighted) occurrence count: a
  first-order per-device traffic figure, not an XLA cost model.
* **derived model** — the collective classes GSPMD must insert for the
  recipe's in/out shardings, computed from the parallel/sharding.py
  tables themselves (so a mutated table shifts this output): grad
  all-reduce vs reduce-scatter over 'data' (the reference's DDP-vs-ZeRO-2
  distinction), the ZeRO-1/2 param refresh all-gather, the ZeRO-3 param
  gathers (hoisting-aware: one per optimizer step when the round-6 trade
  applies, one per micro-step otherwise), tp activation psums, sp ring
  traffic, MoE dispatch, pipe stage boundaries. These are the numbers to
  diff against PERF.md's round-6 overlap model; the decode-side table
  reads against the round-9 decode bytes model (comms bytes vs HBM
  bytes — see PERF.md round 19).

On top of the inventory the auditor checks, per cell:

* **donation** — replicate XLA's input/output buffer aliasing at the
  aval level: every donated leaf (the train step's `donate_argnums=(0,)`
  state, the engine's TPU cache-pool donation contract) must find a
  shape/dtype-matched output leaf; an unmatched donated leaf is a silent
  donation miss (rule ``donation-miss``) — the class of bug that twice
  bit compat.py's checkpoint path.
* **trace budgets** — statically enumerate the engine's distinct program
  signatures (closed-form pow2 bucket set, cross-checked against a
  brute-force sweep of every prompt length) and assert them against the
  obs/retrace.py budgets: step<=1, fused_step<=1, one admit per bucket.
  A bucketing bug that would compile per-length programs fails here at
  lint time (rule ``signature-enumeration`` / ``trace-budget``).
* **unexpected comms** — any explicit collective under the 'single'
  recipe (rule ``unexpected-comms``; the decode hot path must be
  collective-free on one chip), a grad table that silently falls back to
  all-reduce where the recipe family promises reduce-scatter (rule
  ``promised-reduce-scatter``), and overlap=on cells whose rings went
  missing (rule ``overlap-rings-missing``).

The committed golden matrix (`commscheck_golden.json`) is the second
half of the logical-axis-rules refactor gate (ROADMAP): rerun after the
refactor and diff — specs identical is necessary, collectives identical
is the proof. Tracing every one of the 140 shardcheck cells costs ~10
min at the 1.5B rung, so the default `COMMSCHECK_TRACE=auto` scope
traces the 124M (+moe) configs over the full recipe x mesh grid and the
ladder rungs at representative recipes, while the derived model covers
EVERY cell; `full` traces everything, `off` none.

No accelerator is touched: the CLI requests `COMMSCHECK_DEVICES` virtual
CPU devices (compat.request_cpu_devices) so real meshes up to 4x2 exist
for tracing, and nothing is ever compiled or executed.

CLI::

    python -m distributed_pytorch_tpu.parallel.commscheck --all --json -
    python -m distributed_pytorch_tpu.parallel.commscheck --all \
        --json commscheck_report.json            # + golden diff
    python -m distributed_pytorch_tpu.parallel.commscheck --update-golden
    python -m distributed_pytorch_tpu.parallel.commscheck \
        --cell "train/gpt2_124m/fsdp/2x1"

Exit status: nonzero iff an ERROR finding surfaced or the report
diverged from the golden matrix.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
from collections import Counter
from typing import Any, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from distributed_pytorch_tpu.config import (LLMConfig, PARALLELISM_RECIPES,
                                            PRESETS, TrainConfig, knob)
from distributed_pytorch_tpu.parallel import context, sharding as shd
from distributed_pytorch_tpu.parallel.mesh import MeshPlan, build_mesh
from distributed_pytorch_tpu.parallel.shardcheck import (
    AbstractMesh, DEFAULT_MESHES, Finding, mesh_sizes_for, param_shapes)

GOLDEN_PATH = os.path.join(os.path.dirname(__file__),
                           "commscheck_golden.json")

#: collective primitive -> reporting family. `psum_scatter` is jax's
#: reduce-scatter; pmin/pmax are all-reduce-shaped (tiny, but on the wire).
COLLECTIVE_FAMILY = {
    "psum": "all_reduce",
    "psum_invariant": "all_reduce",  # psum under shard_map's check_vma
    "pmax": "all_reduce",
    "pmin": "all_reduce",
    "all_gather": "all_gather",
    "psum_scatter": "reduce_scatter",
    "reduce_scatter": "reduce_scatter",
    "ppermute": "ppermute",
    "pshuffle": "ppermute",
    "all_to_all": "all_to_all",
}

# audit-wide shape choices: one batch size divisible by every matrix
# 'data' size (1/2/4) so eval_shape caches per config, and accum=2 so
# the micro-batch scan's trip weighting is visible in the tables
AUDIT_BATCH = 4
AUDIT_ACCUM = 2

# engine audit geometry (gpt2_124m cells): DecodeEngine defaults
ENGINE_SLOTS = 8
ENGINE_MIN_BUCKET = 16
ENGINE_BLOCK = 16
ENGINE_CHUNK = 64
ENGINE_SPEC_K = 4   # speculative draft length audited (SPEC_K default)


@dataclasses.dataclass
class CommsReport:
    """One audited cell. `collectives` is the explicit jaxpr inventory,
    `derived` the GSPMD comms model from the spec tables, `donation` the
    per-family aval-level aliasing report, `signatures` (decode cells)
    the static program enumeration vs retrace budgets."""

    key: str
    role: str                  # train | decode
    preset: str
    recipe: str
    mesh: dict
    variant: str = ""
    traced: bool = False
    n_params: int = 0
    collectives: list = dataclasses.field(default_factory=list)
    derived: list = dataclasses.field(default_factory=list)
    donation: dict = dataclasses.field(default_factory=dict)
    signatures: dict = dataclasses.field(default_factory=dict)
    findings: list = dataclasses.field(default_factory=list)

    @property
    def errors(self) -> list:
        return [f for f in self.findings if f.severity == "error"]

    @property
    def ok(self) -> bool:
        return not self.errors

    def to_dict(self) -> dict:
        return {"key": self.key, "role": self.role, "preset": self.preset,
                "recipe": self.recipe, "mesh": self.mesh,
                "variant": self.variant, "traced": self.traced,
                "n_params": self.n_params, "ok": self.ok,
                "collectives": self.collectives, "derived": self.derived,
                "donation": self.donation, "signatures": self.signatures,
                "findings": [f.to_dict() for f in self.findings]}


# ----------------------------------------------------------------------
# jaxpr walk
# ----------------------------------------------------------------------

def _iter_jaxprs(v) -> Iterable:
    """Yield every (open) jaxpr reachable from one eqn param value —
    duck-typed so ClosedJaxpr, Jaxpr and containers of either all work."""
    if hasattr(v, "jaxpr") and hasattr(getattr(v, "jaxpr"), "eqns"):
        yield v.jaxpr
    elif hasattr(v, "eqns"):
        yield v
    elif isinstance(v, (list, tuple)):
        for w in v:
            yield from _iter_jaxprs(w)


def _eqn_axes(eqn) -> tuple:
    for key in ("axes", "axis_name"):
        if key in eqn.params:
            v = eqn.params[key]
            if isinstance(v, (list, tuple)):
                return tuple(sorted(str(a) for a in v))
            return (str(v),)
    return ()


def _eqn_bytes(eqn) -> int:
    """Operand bytes of one collective eqn. Inside shard_map bodies the
    avals are PER-SHARD shapes, so this is per-device traffic to first
    order (an all-gather's receive side is (n-1)x larger; we count the
    send side uniformly and document the convention)."""
    total = 0
    for var in eqn.invars:
        aval = getattr(var, "aval", None)
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        if shape is None or dtype is None:
            continue
        total += int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
    return total


def collective_inventory(jaxpr) -> list:
    """Recursive inventory of explicit collectives in a (closed) jaxpr:
    [{family, prim, axes, count, bytes}], scan-weighted, sorted. Accepts
    a ClosedJaxpr, a Jaxpr, or a `jax.stages.Traced`."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)   # ClosedJaxpr/Traced -> Jaxpr
    acc: dict = {}

    def walk(jx, weight: int):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            fam = COLLECTIVE_FAMILY.get(name)
            if fam is not None:
                key = (fam, name, _eqn_axes(eqn))
                rec = acc.setdefault(key, [0, 0])
                rec[0] += weight
                rec[1] += weight * _eqn_bytes(eqn)
            # scan bodies execute `length` times per outer execution;
            # while_loop trip counts are unknowable statically (weight 1,
            # like cond branches — an undercount, never an overcount)
            sub_w = weight * int(eqn.params["length"]) \
                if name == "scan" and "length" in eqn.params else weight
            for v in eqn.params.values():
                for sub in _iter_jaxprs(v):
                    walk(sub, sub_w)

    walk(jaxpr, 1)
    return [{"family": fam, "prim": prim, "axes": list(axes),
             "count": int(cnt), "bytes": int(nbytes)}
            for (fam, prim, axes), (cnt, nbytes) in
            sorted(acc.items(), key=lambda kv: kv[0])]


# ----------------------------------------------------------------------
# donation (aval-level aliasing)
# ----------------------------------------------------------------------

def donation_report(traced) -> dict:
    """Replicate XLA's donated-buffer aliasing at the aval level: a
    donated input leaf is CONSUMED iff an output leaf of identical
    (shape, dtype) remains unclaimed; anything else is a silent donation
    miss — on TPU the buffer is invalidated anyway and the memory win
    quietly evaporates."""
    def _aval(info):
        return getattr(info, "aval", None) or getattr(info, "_aval")

    args = jax.tree_util.tree_leaves(
        traced.args_info, is_leaf=lambda x: hasattr(x, "donated"))
    outs = jax.tree_util.tree_leaves(
        traced.out_info,
        is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "dtype"))
    pool = Counter((tuple(o.shape), str(np.dtype(o.dtype))) for o in outs)
    donated = consumed = donated_bytes = 0
    missed = []
    for a in args:
        if not getattr(a, "donated", False):
            continue
        aval = _aval(a)
        key = (tuple(aval.shape), str(np.dtype(aval.dtype)))
        donated += 1
        donated_bytes += (int(np.prod(key[0], dtype=np.int64))
                          * np.dtype(aval.dtype).itemsize)
        if pool.get(key, 0) > 0:
            pool[key] -= 1
            consumed += 1
        else:
            missed.append({"shape": list(key[0]), "dtype": key[1]})
    return {"donated": donated, "consumed": consumed,
            "donated_bytes": int(donated_bytes),
            "n_missed": len(missed), "missed": missed[:8]}


def _donation_findings(report: CommsReport, family: str, don: dict) -> None:
    if don["n_missed"]:
        report.findings.append(Finding(
            "donation-miss", "error", "donation", family,
            f"{don['n_missed']} of {don['donated']} donated leaves have "
            f"no shape/dtype-matched output (first: {don['missed'][0]}) — "
            "the buffer is invalidated but never reused"))


# ----------------------------------------------------------------------
# derived GSPMD comms model (spec tables -> collective classes)
# ----------------------------------------------------------------------

def _n_params(cfg: LLMConfig) -> int:
    return sum(int(np.prod(l.shape, dtype=np.int64))
               for l in jax.tree_util.tree_leaves(param_shapes(cfg)))


def _large_leaf_axis_use(specs, shapes, axis, total: int) -> bool:
    """Does any LARGE leaf's spec mention `axis` (None: any axis at all)?
    (mirrors shardcheck's LARGE_FRAC convention: tiny biases/norms
    replicate legitimately)."""
    flat_specs = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, shd.P))
    flat_shapes = jax.tree_util.tree_leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    for spec, shape in zip(flat_specs, flat_shapes):
        if int(np.prod(shape, dtype=np.int64)) < 0.01 * total:
            continue
        for dim in spec:
            names = dim if isinstance(dim, tuple) else (dim,)
            if (axis in names) if axis is not None else \
                    any(n is not None for n in names):
                return True
    return False


def derived_train_comms(cfg: LLMConfig, recipe: str, sizes: dict,
                        train_cfg: TrainConfig,
                        accum: int = AUDIT_ACCUM) -> tuple:
    """(entries, findings): the collective classes GSPMD must insert for
    this recipe's shardings, with first-order per-device bytes/step —
    computed FROM the sharding.py tables, so a table regression moves
    these numbers (and the golden diff). Conventions: fp32 grads/opt
    (P*4 bytes), compute-dtype activations/param-gathers, global batch
    `AUDIT_BATCH` split over 'data', accum micro-steps per optimizer
    step."""
    entries: list = []
    findings: list = []
    if recipe == "single":
        return entries, findings
    mesh = AbstractMesh(sizes)
    data, model_ax = sizes.get("data", 1), sizes.get("model", 1)
    seq, expert, pipe = (sizes.get("seq", 1), sizes.get("expert", 1),
                         sizes.get("pipe", 1))
    p_shapes_tree = param_shapes(cfg)
    shape_tuples = jax.tree_util.tree_map(lambda l: tuple(l.shape),
                                          p_shapes_tree)
    total = _n_params(cfg)
    p4 = total * 4
    act = jnp.dtype(train_cfg.compute_dtype).itemsize
    pc = total * act
    b_loc = max(1, train_cfg.batch_size // max(1, data))
    tok_bytes = b_loc * cfg.block_size * cfg.n_embd * act

    if data > 1:
        p_specs = shd.params_pspecs(p_shapes_tree, recipe, mesh)
        g_specs = shd.grads_pspecs(shape_tuples, p_specs, recipe, mesh)
        grads_sharded = _large_leaf_axis_use(g_specs, shape_tuples,
                                             "data", total)
        if grads_sharded:
            # constrained-sharded accumulator: reduce-scatter per
            # micro-step (the round-6 ring keeps them off the critical
            # path under overlap=on)
            entries.append({"origin": "grads", "family": "reduce_scatter",
                            "axis": "data", "bytes": p4 * accum})
        else:
            # replicated accumulator: ONE deferred all-reduce per step
            entries.append({"origin": "grads", "family": "all_reduce",
                            "axis": "data", "bytes": p4})
        # credit sharding on ANY axis: composed recipes (zero2 at a BxT
        # grid with model>1) inherit the TP spec for TP-ruled leaves, so
        # those grads shard over 'model' instead of 'data' — still not
        # replicated, still not a silent all-reduce of full buffers.
        if recipe in shd._GRAD_SHARDED and not _large_leaf_axis_use(
                g_specs, shape_tuples, None, total):
            findings.append(Finding(
                "promised-reduce-scatter", "error", "derived", "grads",
                f"recipe {recipe!r} is in the sharded-grad family but the "
                "grad table left large leaves replicated — GSPMD will "
                "emit an all-reduce where the recipe promises "
                "reduce-scatter"))
        if recipe in shd._PARAM_SHARDED:
            hoisted = (getattr(train_cfg, "overlap", "auto") == "on"
                       and accum > 1)
            entries.append({"origin": "param-gather",
                            "family": "all_gather", "axis": "data",
                            "bytes": pc * (1 if hoisted else accum),
                            "hoisted": hoisted})
        elif recipe in shd._OPT_SHARDED:
            # ZeRO-1/2: params replicated, each shard updates its slice,
            # one param refresh all-gather per optimizer step
            entries.append({"origin": "zero-param-refresh",
                            "family": "all_gather", "axis": "data",
                            "bytes": p4})
    if model_ax > 1:
        # 2 psums/layer forward (attn proj + mlp down) + their transposes
        entries.append({"origin": "tp-activations", "family": "all_reduce",
                        "axis": "model",
                        "bytes": 4 * cfg.n_layer * accum * tok_bytes})
    if seq > 1:
        # ring attention: K+V circulate seq-1 hops per layer, fwd + bwd
        entries.append({"origin": "sp-ring", "family": "ppermute",
                        "axis": "seq",
                        "bytes": (4 * (seq - 1) * cfg.n_layer * accum
                                  * tok_bytes // seq)})
    if expert > 1 and cfg.moe:
        entries.append({"origin": "moe-dispatch", "family": "all_to_all",
                        "axis": "expert",
                        "bytes": 2 * cfg.n_layer * accum * tok_bytes})
    if pipe > 1:
        # schedule-aware (ISSUE 19): the carry schedule crosses each of
        # the pipe-1 stage boundaries once per direction with the full
        # local batch; interleaved-1F1B instead rolls the (S, b, T, C)
        # buffer once per tick — a per-chunk hand-back of one microbatch
        # (tok_bytes/M) — scan-weighted over the fwd ticks + the mirrored
        # bwd, exactly how collective_inventory weighs the traced scan.
        from distributed_pytorch_tpu.models import pipeline as pipe_mod
        pcfg = dataclasses.replace(cfg, pp_stages=pipe)
        if pipe_mod.resolve_schedule(pcfg) == "1f1b":
            vpp = pipe_mod.resolve_vpp(pcfg)
            M = pcfg.pp_microbatches
            if M <= 0:  # run_pipeline's auto pick, model-level batch
                M = min(train_cfg.batch_size, 2 * pipe)
                while train_cfg.batch_size % M:
                    M -= 1
            sched = pipe_mod._build_1f1b_schedule(pipe, vpp, M)
            entries.append({"origin": "pipe-1f1b", "family": "ppermute",
                            "axis": "pipe", "vpp": vpp,
                            "n_microbatches": M,
                            "ticks": 2 * sched.ticks,
                            "bytes": (2 * sched.ticks * accum
                                      * tok_bytes // M)})
        else:
            entries.append({"origin": "pipe-boundary",
                            "family": "ppermute", "axis": "pipe",
                            "bytes": 2 * (pipe - 1) * accum * tok_bytes})
    return entries, findings


def derived_decode_comms(cfg: LLMConfig, sizes: dict,
                         n_slots: int = ENGINE_SLOTS) -> list:
    """Decode-step GSPMD comms model: under tp the per-token activation
    psums (2/layer, n_slots single-token rows); the paged pool's 'data'
    block sharding moves bytes only as a function of live positions, so
    it has no static per-step figure — the explicit inventory and the
    round-9 HBM model carry that side."""
    model_ax = sizes.get("model", 1)
    if model_ax <= 1:
        return []
    act = 2  # serving compute dtype: bf16
    return [{"origin": "tp-activations", "family": "all_reduce",
             "axis": "model",
             "bytes": 2 * cfg.n_layer * n_slots * cfg.n_embd * act}]


# ----------------------------------------------------------------------
# train-side audit
# ----------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _train_pieces(cfg: LLMConfig, batch_size: int):
    """(model, tx, state_shapes) shared across every recipe/mesh cell of
    one config: the state init's eval_shape depends only on the config
    and batch size (recipe shardings are applied later), and tracing it
    once per config keeps the matrix inside the CI budget."""
    from distributed_pytorch_tpu.train.state import (build_model,
                                                     init_train_state,
                                                     make_optimizer)
    tcfg = TrainConfig(parallelism="single", batch_size=batch_size)
    model = build_model(cfg, tcfg)
    tx = make_optimizer(tcfg)
    state_shapes = jax.eval_shape(
        lambda r: init_train_state(r, model, cfg, tx,
                                   batch_size=batch_size),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    return model, tx, state_shapes


def audit_train_cell(preset: str, cfg: LLMConfig, recipe: str,
                     grid: tuple, *, trace: bool,
                     overlap: Optional[str] = None,
                     accum: int = AUDIT_ACCUM,
                     variant: str = "") -> CommsReport:
    """Audit one train-step cell: derived model always; jaxpr inventory
    + donation when `trace` (needs grid[0]*grid[1] local devices)."""
    from distributed_pytorch_tpu.train.step import trace_train_step
    sizes = mesh_sizes_for(recipe, grid)
    key = f"train/{preset}/{recipe}/{grid[0]}x{grid[1]}"
    if variant:
        key += f"/{variant}"
    tcfg_kw = dict(parallelism=recipe, batch_size=AUDIT_BATCH)
    if overlap is not None:
        tcfg_kw["overlap"] = overlap
    tcfg = TrainConfig(**tcfg_kw)
    report = CommsReport(key=key, role="train", preset=preset,
                         recipe=recipe, mesh=sizes, variant=variant,
                         n_params=_n_params(cfg))
    entries, findings = derived_train_comms(cfg, recipe, sizes, tcfg,
                                            accum=accum)
    if variant == "offload":
        # ZeRO-Offload PCIe legs (train/offload.py): full fp32 grads
        # stream to the host and updated params stream back, once per
        # optimizer step per process (the device_get gathers shards) —
        # host transfers, not collectives, so their own family
        p4_full = _n_params(cfg) * 4
        entries = entries + [
            {"origin": "offload-grads", "family": "host_transfer",
             "direction": "to_host", "bytes": p4_full},
            {"origin": "offload-params", "family": "host_transfer",
             "direction": "to_device", "bytes": p4_full}]
    report.derived = entries
    report.findings.extend(findings)
    if not trace:
        return report

    model, tx, state_shapes = _train_pieces(cfg, AUDIT_BATCH)
    mesh = None
    if recipe != "single":
        mesh = build_mesh(MeshPlan(**sizes))
    traced = trace_train_step(model, tx, cfg, tcfg, state_shapes,
                              mesh=mesh, accum=accum)
    report.traced = True
    report.collectives = collective_inventory(traced)
    don = donation_report(traced)
    report.donation["train_step"] = don
    _donation_findings(report, "train_step", don)

    if recipe == "single" and report.collectives:
        report.findings.append(Finding(
            "unexpected-comms", "error", "inventory", "train_step",
            f"{len(report.collectives)} collective kind(s) in a "
            "single-chip trace: " +
            ", ".join(c["prim"] for c in report.collectives)))
    if overlap == "on" and accum == 1 and sizes.get("data", 1) > 1 \
            and recipe in shd._PARAM_SHARDED \
            and not any(c["family"] == "ppermute"
                        for c in report.collectives):
        report.findings.append(Finding(
            "overlap-rings-missing", "error", "inventory", "train_step",
            "overlap=on with per-micro-step gathers promised ppermute "
            "rings (ops/collective_matmul.py) but the trace has none"))
    if variant == "offload":
        # the host half of the split step: the optax update traced over
        # abstract state. Contract: params + opt_state donated AND fully
        # consumed (the moments update in place in host RAM — the
        # kv_tier donated copy-program idiom), and ZERO collectives (a
        # collective in a host program would mean the update somehow
        # still spans the mesh).
        from distributed_pytorch_tpu.train import offload as offload_mod
        htr = offload_mod.trace_host_update(
            tx, state_shapes, anomaly=getattr(tcfg, "anomaly", "warn"))
        don = donation_report(htr)
        report.donation["host_update"] = don
        _donation_findings(report, "host_update", don)
        hinv = collective_inventory(htr)
        if hinv:
            report.findings.append(Finding(
                "unexpected-comms", "error", "inventory", "host_update",
                "collective(s) in the host optimizer update: " +
                ", ".join(c["prim"] for c in hinv)))
    return report


# ----------------------------------------------------------------------
# decode-side audit
# ----------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _engine_pieces(cfg: LLMConfig):
    """(model, variable_shapes) for the decode audit: abstract variables
    from the real model init — moe_state and all — never materialized."""
    from distributed_pytorch_tpu.models.gpt import LLM
    model = LLM(cfg, compute_dtype=jnp.bfloat16)
    dummy = jax.ShapeDtypeStruct((1, cfg.block_size), jnp.int32)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    var_shapes = jax.eval_shape(
        lambda r, d: model.init({"params": r, "dropout": r}, d, d),
        rng, dummy)
    return model, var_shapes


def audit_decode_cell(preset: str, cfg: LLMConfig, recipe: str,
                      grid: tuple, *, chunked: bool,
                      trace: bool) -> CommsReport:
    """Audit one engine cell: trace the step (+ fused step or one
    representative bucket admit) from the SAME factories the engine
    jits, enumerate program signatures, verify cache-pool donation under
    the TPU contract (donate_argnums=(1,) — audited regardless of the
    current backend, where the engine itself skips donation on CPU)."""
    from distributed_pytorch_tpu.engine import decode as eng
    from distributed_pytorch_tpu.models.generate import sample_token
    from distributed_pytorch_tpu.models.gpt import init_paged_cache

    sizes = mesh_sizes_for(recipe, grid)
    variant = "chunked" if chunked else "wave"
    key = f"decode/{preset}/{recipe}/{grid[0]}x{grid[1]}/{variant}"
    report = CommsReport(key=key, role="decode", preset=preset,
                         recipe=recipe, mesh=sizes, variant=variant,
                         n_params=_n_params(cfg))
    report.derived = derived_decode_comms(cfg, sizes)

    max_len = cfg.block_size
    chunk = ENGINE_CHUNK if chunked else 0
    sigs = eng.enumerate_trace_signatures(
        min_bucket=ENGINE_MIN_BUCKET, block_size=ENGINE_BLOCK,
        max_len=max_len, prefill_chunk=chunk, spec_k=ENGINE_SPEC_K)
    # cross-check the closed-form bucket set against a brute-force sweep
    # of every admissible prompt length: a bucketing bug that compiles
    # per-length programs (the classic trace explosion) must fail HERE,
    # not at runtime when the retrace guard starts warning
    brute = sorted({eng.prefill_bucket_for(n, ENGINE_MIN_BUCKET,
                                           ENGINE_BLOCK, max_len)
                    for n in range(1, max_len + 1)})
    budgets = {"step": 1, "fused_step": 1, "spec_step": 1, "promote": 1,
               "admit": len(brute) if not chunked else 0}
    report.signatures = {"enumerated": sigs, "budgets": budgets,
                         "brute_force_buckets": len(brute)}
    if not chunked and sigs["buckets"] != brute:
        report.findings.append(Finding(
            "signature-enumeration", "error", "signatures", "admit",
            f"closed-form bucket set {sigs['buckets']} != brute-force "
            f"sweep over prompt lengths ({len(brute)} buckets)"))
    for fam in ("step", "fused_step", "admit", "spec_step", "promote"):
        if sigs[fam] > budgets[fam]:
            report.findings.append(Finding(
                "trace-budget", "error", "signatures", fam,
                f"{sigs[fam]} static signature(s) exceed the retrace "
                f"budget {budgets[fam]} (obs/retrace.py)"))
    if not trace:
        return report

    model, var_shapes = _engine_pieces(cfg)
    mesh = None if recipe == "single" else build_mesh(MeshPlan(**sizes))
    n_slots = ENGINE_SLOTS
    max_blocks = max_len // ENGINE_BLOCK
    n_blocks = n_slots * max_blocks + 1
    n_blocks += (-n_blocks) % 8
    table_width = max_blocks + (chunk // ENGINE_BLOCK if chunk else 0)
    caches = jax.eval_shape(
        lambda: init_paged_cache(cfg, n_blocks, ENGINE_BLOCK,
                                 dtype=jnp.bfloat16))

    def sample(logits, rng):
        return sample_token(logits, rng, temperature=0.0, top_k=None)

    i32 = jnp.int32
    tok = jax.ShapeDtypeStruct((n_slots,), i32)
    pos = jax.ShapeDtypeStruct((n_slots,), i32)
    live = jax.ShapeDtypeStruct((n_slots,), jnp.bool_)
    bt = jax.ShapeDtypeStruct((n_slots, table_width), i32)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    t = jax.ShapeDtypeStruct((), i32)
    ctx = (context.use_mesh(mesh) if mesh is not None
           else __import__("contextlib").nullcontext())

    # audit the TPU donation contract explicitly — the engine only
    # donates on a TPU backend, but the contract must hold wherever it
    # engages
    with ctx:
        step_tr = jax.jit(eng.make_step_fn(model, sample),
                          donate_argnums=(1,)).trace(
            var_shapes, caches, tok, pos, live, bt, rng, t, None)
        inv = collective_inventory(step_tr)
        don = donation_report(step_tr)
        report.donation["step"] = don
        _donation_findings(report, "step", don)
        # spec-verify program (ISSUE 16): same forward as the step but
        # K+1 positions wide — must add NO collectives beyond the step's
        # own (the single-chip unexpected-comms check covers it below)
        draft = jax.ShapeDtypeStruct((n_slots, ENGINE_SPEC_K), i32)
        dlen = jax.ShapeDtypeStruct((n_slots,), i32)
        spec_tr = jax.jit(
            eng.make_spec_step_fn(model, sample, ENGINE_SPEC_K),
            donate_argnums=(1,)).trace(
            var_shapes, caches, tok, pos, live, bt, rng, t, None,
            draft, dlen)
        inv += collective_inventory(spec_tr)
        don = donation_report(spec_tr)
        report.donation["spec_step"] = don
        _donation_findings(report, "spec_step", don)
        # host-tier promote copy program (ISSUE 17, ops/kv_tier.py):
        # EXACTLY ONE audited program stages any demoted chain back into
        # HBM — fixed (block_size, ...) row shapes per cache leaf plus a
        # scalar block id — and the pool buffers are donated so the
        # promotion recycles the cache allocation in place (the TPU
        # contract; the engine skips donation on CPU). The demote side
        # is a device_get, not a program — nothing to trace.
        from distributed_pytorch_tpu.ops import kv_tier
        rows = jax.tree_util.tree_map(
            lambda pool: jax.ShapeDtypeStruct(pool.shape[1:], pool.dtype),
            caches)
        promote_tr = jax.jit(kv_tier.make_promote_block_fn(),
                             donate_argnums=(0,)).trace(
            caches, rows, jax.ShapeDtypeStruct((), i32))
        inv += collective_inventory(promote_tr)
        don = donation_report(promote_tr)
        report.donation["promote"] = don
        _donation_findings(report, "promote", don)
        if chunked:
            ctoks = jax.ShapeDtypeStruct((1, chunk), i32)
            clen = jax.ShapeDtypeStruct((1,), i32)
            fused_tr = jax.jit(
                eng.make_fused_step_fn(model, sample, n_slots,
                                       table_width),
                donate_argnums=(1,)).trace(
                var_shapes, caches, tok, pos, live, bt, rng, t, None,
                ctoks, t, t, clen, jax.ShapeDtypeStruct((), jnp.bool_))
            inv += collective_inventory(fused_tr)
            don = donation_report(fused_tr)
            report.donation["fused_step"] = don
            _donation_findings(report, "fused_step", don)
        else:
            bucket = ENGINE_CHUNK  # one representative pow2 bucket
            prompt = jax.ShapeDtypeStruct((1, bucket), i32)
            tl = jax.ShapeDtypeStruct((1,), i32)
            admit_tr = jax.jit(eng.make_admit_fn(model, sample),
                               donate_argnums=(1,)).trace(
                var_shapes, caches, tok, pos, live, bt, prompt, t, tl,
                t, rng)
            inv += collective_inventory(admit_tr)
            don = donation_report(admit_tr)
            report.donation[f"admit[{bucket}]"] = don
            _donation_findings(report, f"admit[{bucket}]", don)
    report.traced = True
    # merge the per-family inventories (same prim+axes adds up)
    merged: dict = {}
    for c in inv:
        k = (c["family"], c["prim"], tuple(c["axes"]))
        rec = merged.setdefault(k, [0, 0])
        rec[0] += c["count"]
        rec[1] += c["bytes"]
    report.collectives = [
        {"family": f, "prim": p, "axes": list(a), "count": cnt,
         "bytes": b}
        for (f, p, a), (cnt, b) in sorted(merged.items(),
                                          key=lambda kv: kv[0])]

    if recipe == "single" and report.collectives:
        report.findings.append(Finding(
            "unexpected-comms", "error", "inventory", "decode",
            "collective(s) on the single-chip decode hot path: " +
            ", ".join(c["prim"] for c in report.collectives)))
    return report


# ----------------------------------------------------------------------
# matrix + golden
# ----------------------------------------------------------------------

#: ladder rungs traced under COMMSCHECK_TRACE=auto (representative
#: recipes; the 124M configs trace the full recipe x mesh grid)
AUTO_TRACE_LADDER = (("fsdp", (2, 1)), ("fsdp_tp", (4, 2)))
#: overlap A/B cells (round-6 model): rings vs hoisted gathers
OVERLAP_CELLS = ((1, "overlap-accum1"), (2, "overlap-accum2"))
#: engine cells (gpt2_124m): the round-9 config, wave + chunked, plus a
#: sharded-pool and a tp cell
DECODE_CELLS = (("single", (1, 1), False), ("single", (1, 1), True),
                ("dp", (2, 1), True), ("tp", (1, 2), True))


def _matrix_configs(presets=None, include_moe: bool = True) -> list:
    presets = list(presets or PRESETS)
    configs = [(name, PRESETS[name]()) for name in presets]
    if include_moe:
        configs.append(("gpt2_124m+moe", PRESETS["gpt2_124m"](
            moe=True, n_exp=16, n_shared=2, n_act=8)))
    return configs


def _should_trace(mode: str, preset: str, recipe: str,
                  grid: tuple) -> bool:
    if mode == "off":
        return False
    if mode == "full":
        return True
    if preset in ("gpt2_124m", "gpt2_124m+moe"):
        return True
    return (recipe, grid) in AUTO_TRACE_LADDER


def check_matrix(presets: Optional[Iterable[str]] = None,
                 recipes: Optional[Iterable[str]] = None,
                 meshes: Iterable[tuple] = DEFAULT_MESHES,
                 trace_mode: Optional[str] = None,
                 progress=None) -> list:
    """The full comms matrix: every shardcheck cell gets the derived
    model + findings; cells inside the trace scope additionally get the
    jaxpr inventory + donation audit; the gpt2_124m engine cells get the
    decode audit. Returns CommsReports in deterministic order."""
    trace_mode = trace_mode or knob("COMMSCHECK_TRACE")
    recipes = list(recipes or PARALLELISM_RECIPES)
    meshes = [tuple(m) for m in meshes]
    reports: list = []
    for pname, cfg in _matrix_configs(presets):
        for recipe in recipes:
            for grid in meshes:
                if recipe == "single" and grid != (1, 1):
                    continue
                trace = _should_trace(trace_mode, pname, recipe, grid)
                if progress:
                    progress(f"train/{pname}/{recipe}/"
                             f"{grid[0]}x{grid[1]}"
                             + (" [trace]" if trace else ""))
                reports.append(audit_train_cell(
                    pname, cfg, recipe, grid, trace=trace))
    # overlap A/B (124M, fsdp, 2x1): accum=1 keeps the in-scan rings,
    # accum=2 hoists the gathers — both shapes of the round-6 trade
    cfg_124 = PRESETS["gpt2_124m"]()
    if "fsdp" in recipes and (2, 1) in meshes and (
            presets is None or "gpt2_124m" in list(presets)):
        for accum, variant in OVERLAP_CELLS:
            if progress:
                progress(f"train/gpt2_124m/fsdp/2x1/{variant} [trace]")
            reports.append(audit_train_cell(
                "gpt2_124m", cfg_124, "fsdp", (2, 1),
                trace=trace_mode != "off", overlap="on", accum=accum,
                variant=variant))
        # ZeRO-Offload host-transfer audit (ISSUE 19): PCIe legs in the
        # derived model + the host update's donation/zero-collective
        # contract
        if progress:
            progress("train/gpt2_124m/fsdp/2x1/offload [trace]")
        reports.append(audit_train_cell(
            "gpt2_124m", cfg_124, "fsdp", (2, 1),
            trace=trace_mode != "off", variant="offload"))
        for recipe, grid, chunked in DECODE_CELLS:
            if recipe not in recipes:
                continue
            if progress:
                progress(f"decode/gpt2_124m/{recipe}/"
                         f"{grid[0]}x{grid[1]}/"
                         f"{'chunked' if chunked else 'wave'}")
            reports.append(audit_decode_cell(
                "gpt2_124m", cfg_124, recipe, grid, chunked=chunked,
                trace=trace_mode != "off"))
    return reports


def check_cells(keys: Iterable[str],
                trace_mode: str = "full") -> list:
    """Audit specific cells by report key (the golden-matrix keys) —
    the unit tests' entry: a handful of cells in seconds instead of the
    whole matrix in minutes."""
    out = []
    for key in keys:
        parts = key.split("/")
        role, preset, recipe, mesh = parts[:4]
        variant = parts[4] if len(parts) > 4 else ""
        grid = tuple(int(x) for x in mesh.split("x"))
        if preset == "gpt2_124m+moe":
            cfg = PRESETS["gpt2_124m"](moe=True, n_exp=16, n_shared=2,
                                       n_act=8)
        else:
            cfg = PRESETS[preset]()
        trace = trace_mode != "off"
        if role == "decode":
            out.append(audit_decode_cell(preset, cfg, recipe, grid,
                                         chunked=variant == "chunked",
                                         trace=trace))
        elif variant.startswith("overlap-accum"):
            out.append(audit_train_cell(
                preset, cfg, recipe, grid, trace=trace, overlap="on",
                accum=int(variant[-1]), variant=variant))
        elif variant == "offload":
            out.append(audit_train_cell(preset, cfg, recipe, grid,
                                        trace=trace, variant=variant))
        else:
            out.append(audit_train_cell(preset, cfg, recipe, grid,
                                        trace=trace))
    return out


def reports_payload(reports: list, trace_mode: str) -> dict:
    return {"version": 1, "trace_mode": trace_mode,
            "ok": all(r.ok for r in reports),
            "checked": len(reports),
            "errors": sum(len(r.errors) for r in reports),
            "reports": {r.key: r.to_dict() for r in reports}}


def _diff_value(path: str, a, b, out: list) -> None:
    if isinstance(a, dict) and isinstance(b, dict):
        for k in sorted(set(a) | set(b)):
            if k not in a:
                out.append(f"{path}.{k}: missing in golden")
            elif k not in b:
                out.append(f"{path}.{k}: missing in report")
            else:
                _diff_value(f"{path}.{k}", a[k], b[k], out)
    elif isinstance(a, list) and isinstance(b, list):
        if len(a) != len(b):
            out.append(f"{path}: length {len(b)} != golden {len(a)}")
        else:
            for i, (x, y) in enumerate(zip(a, b)):
                _diff_value(f"{path}[{i}]", x, y, out)
    elif a != b:
        out.append(f"{path}: {b!r} != golden {a!r}")


def diff_golden(payload: dict, golden: dict, limit: int = 40) -> list:
    """Structural diff of a report payload against the committed golden
    matrix. Returns human-readable difference lines (empty = identical).
    Only cells present in BOTH are compared field-by-field; added/
    missing cells are reported as such."""
    diffs: list = []
    if payload.get("trace_mode") != golden.get("trace_mode"):
        diffs.append(
            f"trace_mode: {payload.get('trace_mode')!r} != golden "
            f"{golden.get('trace_mode')!r} (rerun with the golden's "
            "COMMSCHECK_TRACE or --update-golden)")
        return diffs
    g_reports = golden.get("reports", {})
    p_reports = payload.get("reports", {})
    for key in sorted(set(g_reports) | set(p_reports)):
        if key not in p_reports:
            diffs.append(f"{key}: cell missing from report")
        elif key not in g_reports:
            diffs.append(f"{key}: new cell not in golden")
        else:
            _diff_value(key, g_reports[key], p_reports[key], diffs)
        if len(diffs) >= limit:
            diffs.append(f"... (diff truncated at {limit} lines)")
            break
    return diffs


def load_golden(path: str = GOLDEN_PATH) -> Optional[dict]:
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


# ----------------------------------------------------------------------
# CLI
# ----------------------------------------------------------------------

def format_report(r: CommsReport) -> str:
    mesh = ",".join(f"{a}={s}" for a, s in r.mesh.items() if s > 1) \
        or "1 device"
    head = (f"commscheck: {r.key} [{mesh}]"
            f"{' traced' if r.traced else ''} — "
            f"{len(r.collectives)} explicit kind(s), "
            f"{len(r.derived)} derived class(es)")
    lines = [head]
    for c in r.collectives:
        lines.append(f"  explicit {c['prim']}@{','.join(c['axes'])}: "
                     f"x{c['count']}, {c['bytes'] / 2**20:.1f} MiB")
    for d in r.derived:
        lines.append(f"  derived  {d['family']}@{d['axis']} "
                     f"({d['origin']}): {d['bytes'] / 2**20:.1f} MiB/step")
    for fam, don in r.donation.items():
        lines.append(f"  donation {fam}: {don['consumed']}/"
                     f"{don['donated']} consumed"
                     + (f", {don['n_missed']} MISSED"
                        if don["n_missed"] else ""))
    if r.signatures:
        sig = r.signatures["enumerated"]
        lines.append(f"  signatures: step={sig['step']} "
                     f"fused={sig['fused_step']} admit={sig['admit']} "
                     f"spec={sig.get('spec_step', 0)} "
                     f"promote={sig.get('promote', 0)} "
                     f"(budgets {r.signatures['budgets']})")
    for f in r.findings:
        lines.append(f"  [{f.severity.upper()}] {f.rule} "
                     f"({f.table}/{f.path}): {f.detail}")
    if r.ok:
        lines.append("  OK")
    return "\n".join(lines)


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m distributed_pytorch_tpu.parallel.commscheck",
        description="device-free static comms audit (collectives, "
                    "donation, trace budgets) over the shardcheck matrix")
    ap.add_argument("--all", action="store_true",
                    help="audit the full matrix and diff the golden")
    ap.add_argument("--cell", action="append", default=[],
                    metavar="KEY", help="audit one cell by golden key, "
                    "e.g. train/gpt2_124m/fsdp/2x1 (repeatable)")
    ap.add_argument("--trace", choices=("auto", "full", "off"),
                    default=None,
                    help="jaxpr-trace scope (default: COMMSCHECK_TRACE)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the machine-readable report ('-'=stdout)")
    ap.add_argument("--golden", metavar="PATH", default=GOLDEN_PATH,
                    help="golden matrix path")
    ap.add_argument("--update-golden", action="store_true",
                    help="regenerate the golden matrix file")
    ap.add_argument("--no-golden", action="store_true",
                    help="skip the golden diff")
    ap.add_argument("--aot-store", metavar="DIR", default=None,
                    help="cross-check an AOT program store's manifests "
                         "against enumerate_trace_signatures (an "
                         "uncovered signature or a stale key the engine "
                         "can never request fails, same as a golden "
                         "divergence)")
    args = ap.parse_args(argv)

    # virtual CPU devices for the traced meshes — BEFORE any backend use
    from distributed_pytorch_tpu import compat
    compat.request_cpu_devices(knob("COMMSCHECK_DEVICES"))

    trace_mode = args.trace or knob("COMMSCHECK_TRACE")
    if args.cell:
        reports = check_cells(args.cell, trace_mode=trace_mode)
    elif args.all or args.update_golden:
        import time
        t0 = time.time()

        def progress(msg):
            print(f"[{time.time() - t0:6.1f}s] {msg}", file=sys.stderr)
        reports = check_matrix(trace_mode=trace_mode, progress=progress)
    elif args.aot_store:
        reports = []   # store-only invocation: just the cross-check
    else:
        ap.error("one of --all / --update-golden / --cell / "
                 "--aot-store is required")

    payload = reports_payload(reports, trace_mode)
    if args.update_golden:
        with open(args.golden, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"golden matrix -> {args.golden} "
              f"({payload['checked']} cells)")
        return 0 if payload["ok"] else 1

    diffs: list = []
    if not args.no_golden and (args.all or args.cell):
        golden = load_golden(args.golden)
        if golden is None:
            print(f"WARNING: no golden matrix at {args.golden} "
                  "(run --update-golden)", file=sys.stderr)
        elif args.cell:
            # per-cell comparison only (no matrix-level counters): the
            # unit-test path — a few cells in seconds
            for key, rep in payload["reports"].items():
                if key not in golden.get("reports", {}):
                    diffs.append(f"{key}: cell not in golden")
                else:
                    _diff_value(key, golden["reports"][key], rep, diffs)
        else:
            diffs = diff_golden(payload, golden)

    if args.json == "-":
        print(json.dumps(payload, indent=1, sort_keys=True))
    else:
        for r in reports:
            if not r.ok or not (args.all or args.update_golden):
                print(format_report(r))
        n_err = payload["errors"]
        print(f"commscheck: {payload['checked']} cell(s), "
              f"{n_err} error(s), trace={trace_mode}, "
              f"golden {'DIVERGED' if diffs else 'ok'}")
        if args.json:
            with open(args.json, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            print(f"report -> {args.json}")
    for d in diffs:
        print(f"golden diff: {d}", file=sys.stderr)

    # AOT store cross-check (ISSUE 18): the store's warm manifest set
    # must equal the engine's static program enumeration — the same
    # closed-form universe the trace-budget audit above validates.
    aot_errors: list = []
    if args.aot_store:
        from distributed_pytorch_tpu.parallel import aot_store as aot_mod
        aot_errors = aot_mod.crosscheck(aot_mod.AOTStore(args.aot_store))
        for e in aot_errors:
            print(f"aot-store diff: {e}", file=sys.stderr)
        print(f"aot-store cross-check: "
              f"{'DIVERGED' if aot_errors else 'ok'} ({args.aot_store})")
    return 0 if payload["ok"] and not diffs and not aot_errors else 1


if __name__ == "__main__":
    sys.exit(main())
