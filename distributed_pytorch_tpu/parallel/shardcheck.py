"""Device-free sharding validation (ISSUE 12): prove the recipe spec
tables (parallel/sharding.py) against a mesh SHAPE before paying for a
TPU slice.

Every past sharding regression in this repo was silent at the spec layer:
round 1 shipped `tkn_emb` fully replicated under tp (39% of the 124M
params duplicated per model shard) and nothing failed — GSPMD happily
compiles a replicated spec, the step just eats HBM and bandwidth. This
module walks the ACTUAL table outputs — `params_pspecs`,
`shard_like_params` (optimizer moments), `grads_pspecs`, `batch_pspec`,
`decode_cache_pspec`, `moe_dispatch_specs` — for a recipe x model config
x mesh shape and reports, machine-readably:

* ``axis-name``      — a spec names a mesh axis that does not exist;
* ``axis-reuse``     — one spec uses the same mesh axis on two dims
                       (GSPMD rejects this at compile time; here it costs
                       milliseconds, not a slice);
* ``divisibility``   — a sharded dim not divisible by its axis size(s);
* ``replicated-large`` — a tensor >1% of the params left fully
                       replicated under a recipe whose table contract
                       says this tensor class shards (the round-1 bug);
* ``opt-consistency``  — optimizer moments violating the recipe table:
                       ZeRO-1+ must shard large moments over 'data';
                       the param-sharded family must match param specs;
* ``grad-consistency`` — same for the grad accumulator (_GRAD_SHARDED);
* ``cache``          — decode KV buffers with a dead head or pool axis
                       (WARN: legitimate for e.g. 25 heads on model=2).

No devices are touched: param shapes come from `jax.eval_shape` of the
real model init (the memplan.param_count pattern — cannot drift from the
model code) and the mesh is a duck-typed shell, because every sharding.py
rule reads only `dict(zip(mesh.axis_names, mesh.devices.shape))`. A 1.5B
x 4x2 check costs milliseconds on a laptop.

CLI::

    python -m distributed_pytorch_tpu.parallel.shardcheck \
        --preset gpt2_1p5b --recipe fsdp_tp --mesh 4x2
    python -m distributed_pytorch_tpu.parallel.shardcheck --all --json r.json

Exit status is nonzero iff any ERROR finding surfaced (warnings pass, so
the real tables stay green across the whole recipe x ladder matrix —
tests/test_shardcheck.py pins that, plus mutation tests proving each rule
fires). `--dryrun` on the main driver and the train-loop startup both
surface the same report.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
from typing import Any, Iterable, Optional

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from distributed_pytorch_tpu.config import (LLMConfig, PARALLELISM_RECIPES,
                                            PRESETS, TrainConfig)
from distributed_pytorch_tpu.parallel import sharding as shd
from distributed_pytorch_tpu.parallel.mesh import (AXES, resolve_plan,
                                                   rung_down)

# fraction of total params above which a leaf counts as "large" for the
# replication / consistency rules
LARGE_FRAC = 0.01

# default mesh shapes for the matrix: single host, 2-chip, 8-chip (4x2)
DEFAULT_MESHES = ((1, 1), (2, 1), (4, 2))

# elastic rung-down re-mesh cells (round 17): the supervisor re-meshes a
# gang of n hosts down to the next power of two after a loss — the spec
# tables must stay green on exactly those shrunken shapes, or an elastic
# restart trades a dead host for a compile error
RUNG_DOWN_GANGS = (2, 3, 5)

# which mesh axis the second grid factor lands on, per recipe; the
# data-family recipes compose tp on the leftover devices (resolve_plan's
# "axis sizes COMPOSE with any recipe" contract)
_SECOND_AXIS = {"tp": "model", "fsdp_tp": "model", "ep": "expert",
                "sp": "seq", "pp": "pipe"}


class AbstractMesh:
    """Duck-typed stand-in for `jax.sharding.Mesh` with ZERO devices.

    Every rule in parallel/sharding.py reads the mesh only as
    `dict(zip(mesh.axis_names, mesh.devices.shape))`, so an empty object
    array of the right shape drives the real tables device-free."""

    def __init__(self, sizes: dict[str, int]):
        self.axis_names = tuple(sizes)
        self.devices = np.empty(tuple(sizes.values()), dtype=object)

    @property
    def shape(self) -> dict[str, int]:
        return dict(zip(self.axis_names, self.devices.shape))


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str        # axis-name | axis-reuse | divisibility | ...
    severity: str    # "error" | "warn"
    table: str       # params | opt | grads | batch | cache | moe-dispatch
    path: str        # pytree path of the offending leaf
    detail: str

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class Report:
    preset: str
    recipe: str
    mesh: dict[str, int]
    n_params: int = 0
    leaves_checked: int = 0
    variant: str = ""    # e.g. 'rung_down:3->2' for re-mesh cells
    findings: list = dataclasses.field(default_factory=list)

    @property
    def errors(self) -> list:
        return [f for f in self.findings if f.severity == "error"]

    @property
    def warnings(self) -> list:
        return [f for f in self.findings if f.severity == "warn"]

    @property
    def ok(self) -> bool:
        return not self.errors

    def to_dict(self) -> dict:
        return {"preset": self.preset, "recipe": self.recipe,
                "mesh": self.mesh, "n_params": self.n_params,
                "leaves_checked": self.leaves_checked,
                "variant": self.variant, "ok": self.ok,
                "findings": [f.to_dict() for f in self.findings]}


# ----------------------------------------------------------------------
# device-free shape harvesting
# ----------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def param_shapes(cfg: LLMConfig):
    """eval_shape of the real model init (memplan.param_count pattern):
    the params pytree as ShapeDtypeStructs — stacked 'blocks' leaves and
    all, so path-sensitive rules see exactly what training sees."""
    from distributed_pytorch_tpu.models.gpt import LLM
    import jax.numpy as jnp

    dummy = jax.ShapeDtypeStruct((1, cfg.block_size), jnp.int32)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    if cfg.pp_stages > 1:
        # pipeline models init via the loop variant + restack, exactly
        # like train/state.init_train_state — the stacked 'blocks' leaves
        # are what the 'pipe' rules see. Restack at the shape level over
        # the CACHED loop-variant shapes: tracing the model init again
        # just to stack it dominates check_matrix otherwise.
        from distributed_pytorch_tpu.models.pipeline import \
            stack_block_params
        loop_shapes = param_shapes(dataclasses.replace(cfg, pp_stages=1))
        return jax.eval_shape(
            lambda p: stack_block_params(p, cfg.n_layer), loop_shapes)
    model = LLM(cfg)
    variables = jax.eval_shape(
        lambda r, x: model.init({"params": r, "dropout": r}, x, x),
        rng, dummy)
    return variables["params"]


@functools.lru_cache(maxsize=None)
def cache_shapes(cfg: LLMConfig, n_blocks: int = 64,
                 block_size: int = 16) -> tuple[tuple[int, ...], ...]:
    """Shapes of the paged decode KV buffers (models/gpt.init_paged_cache
    via eval_shape — no allocation)."""
    from distributed_pytorch_tpu.models.gpt import init_paged_cache
    tree = jax.eval_shape(
        lambda: init_paged_cache(cfg, n_blocks, block_size))
    return tuple(tuple(l.shape) for l in jax.tree_util.tree_leaves(tree))


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "name", p)))
                    for p in path)


def _spec_entries(spec) -> tuple:
    """Normalize a PartitionSpec to a per-dim tuple of axis-name tuples."""
    out = []
    for entry in tuple(spec):
        if entry is None:
            out.append(())
        elif isinstance(entry, (tuple, list)):
            out.append(tuple(entry))
        else:
            out.append((entry,))
    return tuple(out)


# ----------------------------------------------------------------------
# rules over one (spec, shape) pair / one spec tree
# ----------------------------------------------------------------------

def check_spec(spec: P, shape: tuple[int, ...], sizes: dict[str, int],
               *, table: str, path: str) -> list[Finding]:
    """Structural rules for one leaf: axis-name, axis-reuse,
    divisibility. Public — the mutation tests feed corrupted specs here
    and through `check_spec_tree` directly."""
    out: list[Finding] = []
    entries = _spec_entries(spec)
    if len(entries) > len(shape):
        out.append(Finding("rank", "error", table, path,
                           f"spec {spec} has {len(entries)} dims for "
                           f"shape {shape}"))
        return out
    seen: set[str] = set()
    for i, names in enumerate(entries):
        factor = 1
        for name in names:
            if name not in sizes:
                out.append(Finding(
                    "axis-name", "error", table, path,
                    f"dim {i} names mesh axis {name!r}; mesh has "
                    f"{tuple(sizes)}"))
                continue
            if name in seen:
                out.append(Finding(
                    "axis-reuse", "error", table, path,
                    f"mesh axis {name!r} used on more than one dim of "
                    f"{spec}"))
            seen.add(name)
            factor *= sizes[name]
        if factor > 1 and shape[i] % factor != 0:
            out.append(Finding(
                "divisibility", "error", table, path,
                f"dim {i} of shape {shape} not divisible by "
                f"{'*'.join(names)}={factor}"))
    return out


def _is_replicated(spec: P) -> bool:
    return all(not names for names in _spec_entries(spec))


def check_spec_tree(specs: Any, shapes: Any, sizes: dict[str, int],
                    table: str = "params") -> list[Finding]:
    """Structural rules over a whole spec pytree paired with a shape
    pytree (leaves: anything with .shape, or bare shape tuples)."""
    out: list[Finding] = []
    spec_flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    shape_flat = jax.tree_util.tree_leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    for (path, spec), leaf in zip(spec_flat, shape_flat):
        shape = tuple(leaf) if isinstance(leaf, tuple) \
            else tuple(leaf.shape)
        out += check_spec(spec, shape, sizes,
                          table=table, path=_path_str(path))
    return out


# ----------------------------------------------------------------------
# the full recipe contract for one config x mesh
# ----------------------------------------------------------------------

def _flat_params(shapes_tree):
    return jax.tree_util.tree_flatten_with_path(shapes_tree)[0]


def check_config(model_cfg: LLMConfig, recipe: str,
                 sizes: dict[str, int], *, preset: str = "custom",
                 batch_size: Optional[int] = None,
                 variant: str = "") -> Report:
    """Validate every spec table for one recipe on one mesh shape."""
    sizes = {a: int(sizes.get(a, 1)) for a in AXES}
    report = Report(preset=preset, recipe=recipe, mesh=dict(sizes),
                    variant=variant)
    if sizes["pipe"] > 1:
        try:
            model_cfg = dataclasses.replace(model_cfg,
                                            pp_stages=sizes["pipe"])
        except AssertionError as e:
            report.findings.append(Finding(
                "divisibility", "error", "params", "blocks", str(e)))
            return report
    mesh = AbstractMesh(sizes)
    shapes = param_shapes(model_cfg)
    total = sum(int(np.prod(l.shape))
                for l in jax.tree_util.tree_leaves(shapes))
    report.n_params = total
    large = LARGE_FRAC * total

    p_specs = shd.params_pspecs(shapes, recipe, mesh)
    p_flat = _flat_params(shapes)
    spec_flat = jax.tree_util.tree_flatten_with_path(
        p_specs, is_leaf=lambda x: isinstance(x, P))[0]
    spec_by_path = {_path_str(path): spec for path, spec in spec_flat}

    findings = check_spec_tree(p_specs, shapes, sizes, "params")

    # replicated-large: the recipe's table contract says this tensor
    # class shards, the mesh has somewhere to shard it, yet a >1%-of-
    # params leaf came back fully replicated (the round-1 tkn_emb bug)
    data_shards = recipe in shd._PARAM_SHARDED and sizes["data"] > 1
    tp_shards = recipe in ("tp", "fsdp_tp") and sizes["model"] > 1
    for path, leaf in p_flat:
        pstr = _path_str(path)
        n = int(np.prod(leaf.shape))
        if n < large:
            continue
        spec = spec_by_path[pstr]
        if (data_shards or tp_shards) and _is_replicated(spec):
            findings.append(Finding(
                "replicated-large", "error", "params", pstr,
                f"{n / total:.1%} of params ({leaf.shape}) fully "
                f"replicated under recipe {recipe!r} on mesh "
                f"{ {a: s for a, s in sizes.items() if s > 1} }"))

    # optimizer moments (AdamW mu/nu are params-shaped; the mock tree
    # exercises shard_like_params exactly as train/state.py does)
    shapes_tup = jax.tree_util.tree_map(lambda l: tuple(l.shape), shapes)
    opt_tree = {"mu": shapes, "nu": shapes}
    o_specs = shd.shard_like_params(opt_tree, shapes_tup, p_specs,
                                    recipe, mesh)
    findings += check_spec_tree(o_specs, opt_tree, sizes, "opt")
    o_mu = jax.tree_util.tree_flatten_with_path(
        o_specs["mu"], is_leaf=lambda x: isinstance(x, P))[0]
    mu_by_path = {_path_str(path): spec for path, spec in o_mu}
    opt_shards = recipe in shd._OPT_SHARDED and sizes["data"] > 1
    for path, leaf in p_flat:
        pstr = _path_str(path)
        n = int(np.prod(leaf.shape))
        ospec, pspec = mu_by_path[pstr], spec_by_path[pstr]
        if opt_shards and n >= large and _is_replicated(ospec):
            findings.append(Finding(
                "opt-consistency", "error", "opt", pstr,
                f"recipe {recipe!r} is ZeRO-1+ (opt state sharded over "
                f"'data') but a {n / total:.1%}-of-params moment is "
                f"replicated"))
        if recipe in shd._PARAM_SHARDED and not _is_replicated(pspec) \
                and ospec != pspec:
            findings.append(Finding(
                "opt-consistency", "error", "opt", pstr,
                f"param-sharded recipe {recipe!r}: moment spec {ospec} "
                f"!= param spec {pspec}"))

    # grad accumulator
    g_specs = shd.grads_pspecs(shapes_tup, p_specs, recipe, mesh)
    findings += check_spec_tree(g_specs, shapes, sizes, "grads")
    g_flat = jax.tree_util.tree_flatten_with_path(
        g_specs, is_leaf=lambda x: isinstance(x, P))[0]
    g_by_path = {_path_str(path): spec for path, spec in g_flat}
    grad_shards = recipe in shd._GRAD_SHARDED and sizes["data"] > 1
    for path, leaf in p_flat:
        pstr = _path_str(path)
        n = int(np.prod(leaf.shape))
        gspec, pspec = g_by_path[pstr], spec_by_path[pstr]
        if grad_shards and n >= large and _is_replicated(gspec):
            findings.append(Finding(
                "grad-consistency", "error", "grads", pstr,
                f"recipe {recipe!r} is ZeRO-2+ (grad accumulator "
                f"sharded) but a {n / total:.1%}-of-params grad leaf is "
                f"replicated"))
        if not grad_shards and not _is_replicated(gspec):
            findings.append(Finding(
                "grad-consistency", "error", "grads", pstr,
                f"recipe {recipe!r} keeps the grad accumulator "
                f"replicated but got {gspec}"))

    # batch: structure always; divisibility when a batch size is known
    for accum in (False, True):
        bspec = shd.batch_pspec(recipe, mesh, leading_accum=accum)
        bshape = ((1,) if accum else ()) + (
            batch_size or sizes["data"], model_cfg.block_size)
        findings += check_spec(bspec, bshape, sizes, table="batch",
                               path="batch(accum)" if accum else "batch")

    # decode KV cache (pipeline models don't decode — models/gpt.py gate);
    # per-layer buffers share shapes, so findings collapse per unique shape
    if sizes["pipe"] == 1:
        shape_counts: dict[tuple, int] = {}
        for shape in cache_shapes(model_cfg):
            shape_counts[shape] = shape_counts.get(shape, 0) + 1
        # float GQA pools merge their heads into lanes (block_pool.kv_lanes)
        kv_heads = ((model_cfg.n_kv_heads, model_cfg.head_size)
                    if model_cfg.attn != "mla" else None)
        for shape, n_buf in shape_counts.items():
            cspec = shd.decode_cache_pspec(shape, mesh, kv_heads)
            findings += check_spec(cspec, shape, sizes, table="cache",
                                   path=f"kv{shape}x{n_buf}")
            entries = _spec_entries(cspec)
            heads = kv_heads[0] if kv_heads else 1
            if sizes["model"] > 1 and heads > 1 and not entries[2]:
                findings.append(Finding(
                    "cache", "warn", "cache", f"kv{shape}x{n_buf}",
                    f"kv heads ({heads}) replicated across "
                    f"model={sizes['model']} — every model shard holds "
                    f"the full cache ({heads} % {sizes['model']} != 0, or "
                    f"the {shape[2]} merged lanes carry pad)"))

    # MoE dispatch specs are static — validate their axis names/shapes
    if model_cfg.moe:
        tok, w, out_spec = shd.moe_dispatch_specs()
        n_tok = (batch_size or sizes["data"]) * model_cfg.block_size
        findings += check_spec(
            tok, (n_tok, model_cfg.n_embd), sizes,
            table="moe-dispatch", path="tokens")
        findings += check_spec(
            w, (model_cfg.n_routed, model_cfg.n_embd, model_cfg.up_dim),
            sizes, table="moe-dispatch", path="experts_fc")
        findings += check_spec(
            out_spec, (n_tok, model_cfg.n_embd), sizes,
            table="moe-dispatch", path="out")

    report.findings.extend(findings)
    report.leaves_checked = (3 * len(p_flat)  # params + mu/nu
                             + len(g_flat) + 2
                             + (len(cache_shapes(model_cfg))
                                if sizes["pipe"] == 1 else 0))
    return report


def mesh_sizes_for(recipe: str, grid: tuple[int, int]) -> dict[str, int]:
    """Map an 'AxB' grid onto recipe axes: A is always 'data'; B lands on
    the recipe's secondary axis ('model' for the tp family — and as the
    COMPOSED tp axis for the data-family recipes, resolve_plan's
    contract — 'expert'/'seq'/'pipe' for ep/sp/pp)."""
    a, b = grid
    sizes = dict.fromkeys(AXES, 1)
    sizes["data"] = a
    if b > 1:
        sizes[_SECOND_AXIS.get(recipe, "model")] = b
    return sizes


def check_matrix(presets: Optional[Iterable[str]] = None,
                 recipes: Optional[Iterable[str]] = None,
                 meshes: Iterable[tuple[int, int]] = DEFAULT_MESHES,
                 include_moe: bool = True) -> list[Report]:
    """The full golden matrix: every recipe x ladder preset x mesh shape
    (plus a MoE'd 124M under every mesh so 'ep' and the dispatch specs
    are exercised meaningfully, plus the round-17 rung-down re-mesh
    shapes per RUNG_DOWN_GANGS). 'single' is only defined at 1x1."""
    presets = list(presets or PRESETS)
    recipes = list(recipes or PARALLELISM_RECIPES)
    meshes = [tuple(m) for m in meshes]
    configs: list[tuple[str, LLMConfig]] = [
        (name, PRESETS[name]()) for name in presets]
    if include_moe:
        configs.append(("gpt2_124m+moe", PRESETS["gpt2_124m"](
            moe=True, n_exp=16, n_shared=2, n_act=8)))
    out = []
    for pname, cfg in configs:
        for recipe in recipes:
            for grid in meshes:
                if recipe == "single" and grid != (1, 1):
                    continue
                out.append(check_config(
                    cfg, recipe, mesh_sizes_for(recipe, grid),
                    preset=pname))
            if recipe == "single":
                continue
            # round-17 elastic re-mesh shapes: a gang of n survivors
            # rungs down to the next power of two on the data grid
            for n in RUNG_DOWN_GANGS:
                down = rung_down(n)
                out.append(check_config(
                    cfg, recipe, mesh_sizes_for(recipe, (down, 1)),
                    preset=pname, variant=f"rung_down:{n}->{down}"))
    return out


def check_train_config(model_cfg: LLMConfig, train_cfg: TrainConfig,
                       preset: str = "custom") -> Report:
    """The --dryrun / train-startup entry: resolve the mesh plan the run
    would build (falling back to the explicit axis sizes alone when the
    local device count doesn't fit) and check it device-free."""
    recipe = train_cfg.parallelism
    try:
        plan = resolve_plan(
            recipe, jax.device_count(), tp_size=train_cfg.tp_size,
            ep_size=train_cfg.ep_size, sp_size=train_cfg.sp_size,
            pp_size=train_cfg.pp_size, dp_size=train_cfg.dp_size)
        sizes = dict(zip(AXES, plan.axis_sizes()))
    except Exception:
        sizes = {"data": max(train_cfg.dp_size, 1), "seq": train_cfg.sp_size,
                 "expert": train_cfg.ep_size, "model": train_cfg.tp_size,
                 "pipe": train_cfg.pp_size}
    return check_config(model_cfg, recipe, sizes, preset=preset,
                        batch_size=train_cfg.batch_size)


# ----------------------------------------------------------------------
# rendering + CLI
# ----------------------------------------------------------------------

def format_report(report: Report) -> str:
    mesh = ",".join(f"{a}={s}" for a, s in report.mesh.items() if s > 1) \
        or "1 device"
    tag = f" ({report.variant})" if report.variant else ""
    head = (f"shardcheck: {report.preset} x {report.recipe} on "
            f"[{mesh}]{tag} — {report.n_params / 1e6:.0f}M params, "
            f"{report.leaves_checked} leaves")
    lines = [head]
    for f in report.findings:
        lines.append(f"  [{f.severity.upper()}] {f.rule} "
                     f"({f.table}/{f.path}): {f.detail}")
    if report.ok:
        lines.append(f"  OK ({len(report.warnings)} warning(s))"
                     if report.warnings else "  OK")
    return "\n".join(lines)


def reports_to_json(reports: list) -> str:
    return json.dumps({
        "ok": all(r.ok for r in reports),
        "checked": len(reports),
        "errors": sum(len(r.errors) for r in reports),
        "warnings": sum(len(r.warnings) for r in reports),
        "reports": [r.to_dict() for r in reports]}, indent=2)


def _parse_mesh(s: str) -> tuple[int, int]:
    a, _, b = s.lower().partition("x")
    return int(a), int(b or 1)


def main(argv: Optional[list[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m distributed_pytorch_tpu.parallel.shardcheck",
        description="device-free sharding-spec validation")
    ap.add_argument("--preset", choices=sorted(PRESETS), default=None)
    ap.add_argument("--recipe", choices=PARALLELISM_RECIPES, default=None)
    ap.add_argument("--mesh", type=_parse_mesh, default=(1, 1),
                    metavar="AxB", help="device grid, e.g. 4x2 (A='data', "
                    "B=the recipe's secondary axis)")
    ap.add_argument("--moe", action="store_true",
                    help="check the preset with MoE blocks enabled")
    ap.add_argument("--all", action="store_true",
                    help="the full recipe x ladder x mesh matrix")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the machine-readable report here "
                    "('-' for stdout)")
    args = ap.parse_args(argv)

    if args.all:
        reports = check_matrix()
    else:
        if not (args.preset and args.recipe):
            ap.error("--preset and --recipe are required without --all")
        cfg = PRESETS[args.preset]()
        if args.moe:
            cfg = dataclasses.replace(cfg, moe=True)
        reports = [check_config(
            cfg, args.recipe, mesh_sizes_for(args.recipe, args.mesh),
            preset=args.preset)]

    payload = reports_to_json(reports)
    if args.json == "-":
        print(payload)
    else:
        for r in reports:
            if not r.ok or r.warnings or not args.all:
                print(format_report(r))
        n_err = sum(len(r.errors) for r in reports)
        print(f"shardcheck: {len(reports)} config(s), {n_err} error(s), "
              f"{sum(len(r.warnings) for r in reports)} warning(s)")
        if args.json:
            with open(args.json, "w") as f:
                f.write(payload)
            print(f"report -> {args.json}")
    return 0 if all(r.ok for r in reports) else 1


if __name__ == "__main__":
    sys.exit(main())
