"""Round-16 static-analysis subsystem in one suite: the config knob
registry, TraceGuard retrace accounting, scripts/lint.py rules (each
demonstrated by a fixture under tests/lint_fixtures/), and the
device-free shardcheck golden matrix + seeded spec-table mutations.

Named zz_ deliberately: everything here is cheap meta-tooling, and
sorting it last keeps tier-1's wall-clock budget spent on the
compile-heavy kernel/recipe parity suites first.
"""

import ast
import importlib.util
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from distributed_pytorch_tpu import config
from distributed_pytorch_tpu.config import (PARALLELISM_RECIPES, PRESETS,
                                            TrainConfig)
from distributed_pytorch_tpu.obs.retrace import (RetraceError, TraceGuard,
                                                 guarded)
from distributed_pytorch_tpu.parallel import commscheck, shardcheck, \
    sharding as shd
from distributed_pytorch_tpu.parallel.mesh import AXES, MeshPlan, build_mesh

REPO = pathlib.Path(__file__).resolve().parents[1]
FIXTURES = REPO / "tests" / "lint_fixtures"

# scripts/ is not a package — load by path
_spec = importlib.util.spec_from_file_location(
    "repo_lint", REPO / "scripts" / "lint.py")
lint = importlib.util.module_from_spec(_spec)
sys.modules["repo_lint"] = lint  # dataclasses resolve types via sys.modules
_spec.loader.exec_module(lint)


# ---------------------------------------------------------------------------
# config.py env-knob registry
# ---------------------------------------------------------------------------

def test_knob_defaults_read_without_env():
    assert config.knob("GMM_BLOCK_N") == 512
    assert config.knob("TRACE_GUARD") == "warn"
    assert config.knob("FLASH_DECODE") == "auto"


def test_knob_env_override_is_live(monkeypatch):
    """Knob.read consults os.environ per call, so monkeypatch.setenv works
    mid-process — the property the tests depend on."""
    monkeypatch.setenv("GMM_BLOCK_N", "128")
    assert config.knob("GMM_BLOCK_N") == 128
    monkeypatch.delenv("GMM_BLOCK_N")
    assert config.knob("GMM_BLOCK_N") == 512


def test_knob_unregistered_name_fails_loudly():
    with pytest.raises(KeyError):
        config.knob("GMM_BLOK_N")  # typo'd name must not silently default


def test_knob_onoff_validation(monkeypatch):
    monkeypatch.setenv("FLASH_DECODE", "bogus")
    with pytest.raises(ValueError, match="auto|on|off"):
        config.knob("FLASH_DECODE")
    monkeypatch.setenv("FLASH_DECODE", "ON")
    assert config.knob("FLASH_DECODE") == "on"


def test_knobs_table_marks_overrides(monkeypatch):
    monkeypatch.setenv("GMM_BLOCK_M", "1024")
    table = config.knobs_table()
    lines = {ln.split()[0]: ln for ln in table.splitlines()[1:]}
    assert set(lines) == set(config.ENV_KNOBS)
    assert "1024*" in lines["GMM_BLOCK_M"]         # override marker
    assert "*" not in lines["GMM_BLOCK_N"].split()[2]


def test_register_knob_round_trip(monkeypatch):
    k = config.register_knob("TEST_ONLY_KNOB", "7", int, "test fixture")
    try:
        assert config.knob("TEST_ONLY_KNOB") == 7
        monkeypatch.setenv("TEST_ONLY_KNOB", "9")
        assert k.read() == 9
    finally:
        del config.ENV_KNOBS["TEST_ONLY_KNOB"]


# ---------------------------------------------------------------------------
# obs/retrace.py TraceGuard
# ---------------------------------------------------------------------------

def test_guard_counts_and_excess():
    g = TraceGuard("t", budget=2)
    g.mark()
    g.mark()
    assert (g.count, g.excess) == (2, 0)
    g.mark()  # default mode: warn, not raise
    assert (g.count, g.excess) == (3, 1)
    assert g.stats() == {"count": 3, "budget": 2, "excess": 1}


def test_guard_allow_raises_budget():
    g = TraceGuard("t", budget=0)
    g.allow()
    g.mark()
    assert g.excess == 0
    g.allow(2)
    g.mark()
    g.mark()
    assert (g.count, g.budget, g.excess) == (3, 3, 0)


def test_guard_strict_mode_raises(monkeypatch):
    monkeypatch.setenv("TRACE_GUARD", "strict")
    g = TraceGuard("t", budget=1)
    g.mark()
    with pytest.raises(RetraceError, match="trace #2 exceeds budget 1"):
        g.mark()
    assert g.count == 2  # the count still advances


def test_guard_warn_mode_logs(monkeypatch, caplog):
    monkeypatch.setenv("TRACE_GUARD", "warn")
    g = TraceGuard("t", budget=0)
    with caplog.at_level("WARNING", logger="retrace"):
        g.mark()
    assert any("exceeds budget" in r.message for r in caplog.records)


def test_guard_off_mode_is_silent(monkeypatch, caplog):
    monkeypatch.setenv("TRACE_GUARD", "off")
    g = TraceGuard("t", budget=0)
    with caplog.at_level("WARNING", logger="retrace"):
        g.mark()
    assert not caplog.records
    assert g.excess == 1  # still counted for /metrics


def test_guard_expect_window(monkeypatch):
    monkeypatch.setenv("TRACE_GUARD", "strict")
    g = TraceGuard("t", budget=10)
    with g.expect(1):
        g.mark()  # within the window's allowance
    with pytest.raises(RetraceError):
        with g.expect(0):
            g.mark()


def test_guarded_fn_delegates():
    g = TraceGuard("t")
    fn = guarded(lambda x: x + 1, g)
    assert fn(1) == 2
    assert fn.trace_guard is g


def test_guard_jit_integration_counts_traces_not_calls():
    g = TraceGuard("jit", budget=2)

    def f(x):
        g.mark()  # trace-time side effect
        return x * 2

    jf = jax.jit(f)
    jf(jnp.ones((4,)))
    jf(jnp.ones((4,)))          # cache hit: no new trace
    assert g.count == 1
    jf(jnp.ones((8,)))          # new shape: second trace
    assert (g.count, g.excess) == (2, 0)


# ---------------------------------------------------------------------------
# scripts/lint.py: the package must lint clean, every rule must fire
# ---------------------------------------------------------------------------

def _rules(findings):
    return [f.rule for f in findings]


def test_lint_package_is_clean():
    findings = lint.lint_package()
    assert findings == [], "\n".join(str(f) for f in findings)


def test_lint_host_sync_fixture():
    out = lint.lint_file(FIXTURES / "bad_host_sync.py",
                         rules=("host-sync",), rel="ops/fixture.py")
    assert _rules(out) == ["host-sync"] * 8
    # device_get, .item(), float(jnp...), int(device_get) twice, asarray,
    # np.array, .tolist()
    assert sorted(f.line for f in out) == [9, 10, 11, 12, 12, 13, 14, 15]
    # the tagged line (21) must not appear
    assert all(f.line != 21 for f in out)


def test_lint_wallclock_fixture():
    out = lint.lint_file(FIXTURES / "bad_wallclock.py",
                         rules=("wall-clock",), rel="obs/fixture.py")
    assert _rules(out) == ["wall-clock"]
    assert out[0].line == 7


def test_lint_env_read_fixture():
    out = lint.lint_file(FIXTURES / "bad_env.py",
                         rules=("env-read",), rel="serve/fixture.py")
    assert _rules(out) == ["env-read"] * 3
    assert sorted(f.line for f in out) == [7, 8, 9]  # writes not flagged


def test_lint_pallas_gate_fixtures():
    bad = lint.lint_file(FIXTURES / "bad_pallas.py",
                         rules=("pallas-gate",), rel="ops/fixture.py")
    assert _rules(bad) == ["pallas-gate"]
    good = lint.lint_file(FIXTURES / "good_pallas.py",
                          rules=("pallas-gate",), rel="ops/fixture.py")
    assert good == []


def test_lint_rule_scoping_by_path():
    """host-sync only applies to hot-path modules: the same fixture under
    a data-loading path produces no findings with default scoping."""
    hot = lint.lint_file(FIXTURES / "bad_host_sync.py",
                         rel="ops/fixture.py")
    cold = lint.lint_file(FIXTURES / "bad_host_sync.py",
                          rel="data/fixture.py")
    assert any(f.rule == "host-sync" for f in hot)
    assert all(f.rule != "host-sync" for f in cold)


def test_lint_wallclock_scoped_to_obs():
    out = lint.lint_file(FIXTURES / "bad_wallclock.py",
                         rel="train/fixture.py")
    assert all(f.rule != "wall-clock" for f in out)


def test_lint_main_exit_codes(capsys):
    # explicit fixture file -> all rules -> findings -> exit 1 (what CI
    # keys off; the in-process call covers the CLI without paying a
    # subprocess interpreter start)
    assert lint.main([str(FIXTURES / "bad_host_sync.py")]) == 1
    out = capsys.readouterr().out
    assert "[host-sync]" in out
    # whole package -> clean -> exit 0
    assert lint.main([]) == 0


# ---------------------------------------------------------------------------
# shardcheck: the golden matrix
# ---------------------------------------------------------------------------

def test_matrix_green():
    """Every recipe x ladder preset x {1x1, 2x1, 4x2} mesh (plus the MoE
    variant, plus the round-17 rung-down re-mesh shapes) validates with
    zero errors, entirely device-free."""
    reports = shardcheck.check_matrix()
    # 6 configs (5 ladder rungs incl. the 7B pod rung + moe'd 124m) x
    # (9 recipes x (3 meshes + 3 rung-down re-mesh cells) + 'single' at
    # 1x1 only)
    assert len(reports) == 6 * (9 * (3 + 3) + 1)
    bad = [r for r in reports if not r.ok]
    assert not bad, "\n\n".join(shardcheck.format_report(r) for r in bad)
    # the elastic cells are present, labeled, and on the shrunken grids
    rung = [r for r in reports if r.variant.startswith("rung_down:")]
    assert len(rung) == 6 * 9 * 3
    assert {r.variant for r in rung} == {
        "rung_down:2->1", "rung_down:3->2", "rung_down:5->4"}
    for r in rung:
        down = int(r.variant.split("->")[1])
        assert r.mesh["data"] == down
        assert all(s == 1 for a, s in r.mesh.items() if a != "data")


def test_1p5b_tp_cache_warns_but_passes():
    """gpt2_1p5b has 25 heads: under model=2 the decode cache cannot
    shard its kv-head axis — a legitimate WARN, never an error."""
    r = shardcheck.check_config(
        PRESETS["gpt2_1p5b"](), "tp",
        shardcheck.mesh_sizes_for("tp", (1, 2)), preset="gpt2_1p5b")
    assert r.ok
    assert any(f.rule == "cache" for f in r.warnings)


def test_abstract_mesh_matches_real_mesh():
    """The duck-typed AbstractMesh must drive the tables to the exact
    specs a real device mesh produces (8 virtual CPU devices, 4x2)."""
    sizes = {"data": 4, "seq": 1, "expert": 1, "model": 2, "pipe": 1}
    real = Mesh(np.array(jax.devices()[:8]).reshape(4, 1, 1, 2, 1), AXES)
    cfg = PRESETS["gpt2_124m"]()
    shapes = shardcheck.param_shapes(cfg)
    specs_fake = shd.params_pspecs(shapes, "fsdp_tp",
                                   shardcheck.AbstractMesh(sizes))
    specs_real = shd.params_pspecs(shapes, "fsdp_tp", real)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: a == b, specs_fake, specs_real,
        is_leaf=lambda x: isinstance(x, P)))


def test_check_train_config_resolves_mesh():
    r = shardcheck.check_train_config(
        PRESETS["gpt2_124m"](), TrainConfig(parallelism="fsdp",
                                            batch_size=8))
    assert r.ok and r.recipe == "fsdp" and r.n_params > 100e6
    assert r.mesh["data"] == 8  # resolved from the 8 virtual CPU devices


def test_check_train_config_flags_indivisible_batch():
    """batch_size=2 cannot split across data=8 — the dryrun path must say
    so before a run wastes a TPU reservation discovering it."""
    r = shardcheck.check_train_config(
        PRESETS["gpt2_124m"](), TrainConfig(parallelism="fsdp",
                                            batch_size=2))
    assert any(f.rule == "divisibility" and f.table == "batch"
               for f in r.errors)


# ---------------------------------------------------------------------------
# shardcheck mutations: corrupt the tables, watch each rule fire
# ---------------------------------------------------------------------------

def test_mutation_dropped_tp_rule_flags_replicated_large(monkeypatch):
    """Deleting the tkn_emb TP rule reintroduces the round-1 bug (39% of
    the 124M params replicated per model shard) — replicated-large must
    catch it."""
    monkeypatch.setattr(shd, "_TP_RULES", tuple(
        r for r in shd._TP_RULES if r[0] != ("tkn_emb", "embedding")))
    r = shardcheck.check_config(
        PRESETS["gpt2_124m"](), "tp",
        shardcheck.mesh_sizes_for("tp", (1, 2)))
    hits = [f for f in r.errors if f.rule == "replicated-large"]
    assert hits and any("tkn_emb" in f.path for f in hits)
    assert not r.ok


def test_mutation_out_of_range_axis_flags_replicated_large(monkeypatch):
    """Flipping a rule's axis index past the tensor rank silently drops
    the sharding (spec_for_param bounds-checks) — the large c_attn
    kernels come back replicated and the checker flags them."""
    rules = tuple((suffix, 5) if suffix == ("c_attn", "kernel")
                  else (suffix, ax) for suffix, ax in shd._TP_RULES)
    monkeypatch.setattr(shd, "_TP_RULES", rules)
    r = shardcheck.check_config(
        PRESETS["gpt2_124m"](), "tp",
        shardcheck.mesh_sizes_for("tp", (1, 2)))
    assert any(f.rule == "replicated-large" and "c_attn" in f.path
               for f in r.errors)


def test_corrupt_specs_flag_structural_rules():
    """check_spec_tree catches nonexistent axes, axis reuse, and
    indivisible dims on any spec pytree."""
    sizes = {"data": 4, "seq": 1, "expert": 1, "model": 2, "pipe": 1}
    shapes = {"w": (6, 8), "v": (4, 4)}
    specs = {"w": P("bogus", "model"),    # unknown axis + 8 % 2 == 0 fine
             "v": P("data", "data")}      # reuse + 4 % 4 == 0 fine
    findings = shardcheck.check_spec_tree(specs, shapes, sizes)
    rules = {f.rule for f in findings}
    assert "axis-name" in rules and "axis-reuse" in rules

    div = shardcheck.check_spec(P(None, "model"), (8, 7), sizes,
                                table="params", path="w")
    assert [f.rule for f in div] == ["divisibility"]


def test_rank_overflow_flagged():
    sizes = {"data": 2, "seq": 1, "expert": 1, "model": 1, "pipe": 1}
    out = shardcheck.check_spec(P("data", None, None), (4, 4), sizes,
                                table="params", path="w")
    assert [f.rule for f in out] == ["rank"]


def test_indivisible_expert_grid_flagged():
    """16 experts minus 2 shared = 14 routed: an expert axis of 4 cannot
    divide them — the checker must flag what GSPMD would reject on
    hardware."""
    cfg = PRESETS["gpt2_124m"](moe=True, n_exp=16, n_shared=2, n_act=8)
    sizes = shardcheck.mesh_sizes_for("ep", (1, 4))
    r = shardcheck.check_config(cfg, "ep", sizes)
    assert any(f.rule == "divisibility" and "experts" in f.path
               for f in r.errors)


# ---------------------------------------------------------------------------
# shardcheck report plumbing + CLI
# ---------------------------------------------------------------------------

def test_report_json_round_trip():
    r = shardcheck.check_config(
        PRESETS["gpt2_124m"](), "fsdp",
        shardcheck.mesh_sizes_for("fsdp", (4, 1)))
    payload = json.loads(shardcheck.reports_to_json([r]))
    assert payload["ok"] and payload["checked"] == 1
    assert payload["reports"][0]["recipe"] == "fsdp"
    assert payload["reports"][0]["mesh"]["data"] == 4


def test_cli_green_and_red(monkeypatch, capsys, tmp_path):
    assert shardcheck.main(["--preset", "gpt2_124m", "--recipe", "fsdp_tp",
                            "--mesh", "4x2"]) == 0
    out = capsys.readouterr().out
    assert "OK" in out and "0 error(s)" in out

    json_path = tmp_path / "report.json"
    monkeypatch.setattr(shd, "_TP_RULES", ())
    assert shardcheck.main(["--preset", "gpt2_124m", "--recipe", "tp",
                            "--mesh", "1x2", "--json",
                            str(json_path)]) == 1
    payload = json.loads(json_path.read_text())
    assert not payload["ok"] and payload["errors"] > 0


def test_every_recipe_has_a_secondary_axis_mapping():
    """mesh_sizes_for must place the B grid factor on a real axis for
    every recipe (data-family recipes compose tp on it)."""
    for recipe in PARALLELISM_RECIPES:
        sizes = shardcheck.mesh_sizes_for(recipe, (2, 2))
        assert sum(1 for s in sizes.values() if s > 1) == 2
        assert set(sizes) == set(AXES)


# ---------------------------------------------------------------------------
# commscheck: explicit collective inventory (jaxpr walk + bytes math)
# ---------------------------------------------------------------------------

def test_collective_inventory_bytes_hand_computed():
    """One psum over a 2-device data axis: the inventory must price it at
    exactly the PER-SHARD operand aval (shard_map bodies see shard
    shapes), here (4, 4) f32 = 64 bytes."""
    mesh = build_mesh(MeshPlan(data=2))

    def f(x):
        return jax.lax.psum(x, "data")

    # check_vma on (jax.shard_map's default) spells the psum
    # `psum_invariant`; the repo's own traces (check off) emit plain psum
    sm = jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P())
    jaxpr = jax.make_jaxpr(sm)(jnp.zeros((8, 4), jnp.float32))
    inv = commscheck.collective_inventory(jaxpr)
    assert [(c["family"], c["prim"], c["axes"], c["count"], c["bytes"])
            for c in inv] == [("all_reduce", "psum_invariant", ["data"],
                               1, 64)]


def test_collective_inventory_scan_weighting():
    """A psum inside a length-4 scan body executes 4x per step — the
    inventory multiplies count AND bytes by the trip count."""
    from jax.experimental.shard_map import shard_map
    mesh = build_mesh(MeshPlan(data=2))

    def f(x):
        def body(c, xs):
            return c + jax.lax.psum(xs, "data"), None
        out, _ = jax.lax.scan(body, jnp.zeros((4,), jnp.float32), x)
        return out

    # check_rep=False keeps the plain psum primitive (and sidesteps the
    # scan-carry replication-type check) — both spellings must count
    sm = shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P(),
                   check_rep=False)
    jaxpr = jax.make_jaxpr(sm)(jnp.zeros((8, 4), jnp.float32))
    inv = commscheck.collective_inventory(jaxpr)
    # per-shard leading dim 8/2=4 -> scan length 4; operand (4,) f32=16 B
    assert [(c["prim"], c["count"], c["bytes"]) for c in inv] == \
        [("psum", 4, 64)]


# ---------------------------------------------------------------------------
# commscheck: donation verification (aval-level aliasing)
# ---------------------------------------------------------------------------

def test_donation_report_all_consumed():
    def ok(a, b):
        return a + 1.0, b * 2

    tr = jax.jit(ok, donate_argnums=(0, 1)).trace(
        jax.ShapeDtypeStruct((8,), jnp.float32),
        jax.ShapeDtypeStruct((4,), jnp.int32))
    don = commscheck.donation_report(tr)
    assert (don["donated"], don["consumed"], don["n_missed"]) == (2, 2, 0)
    assert don["donated_bytes"] == 8 * 4 + 4 * 4


def test_donation_miss_flagged_as_error():
    """A donated buffer with no shape/dtype-matched output (the dtype
    changed under it) is a silent donation miss — rule donation-miss."""
    def bad(a):
        return a.astype(jnp.float32)

    tr = jax.jit(bad, donate_argnums=(0,)).trace(
        jax.ShapeDtypeStruct((8,), jnp.bfloat16))
    don = commscheck.donation_report(tr)
    assert (don["donated"], don["consumed"], don["n_missed"]) == (1, 0, 1)
    assert don["missed"] == [{"shape": [8], "dtype": "bfloat16"}]
    rep = commscheck.CommsReport(key="t", role="train", preset="p",
                                 recipe="single", mesh={})
    commscheck._donation_findings(rep, "step", don)
    assert [f.rule for f in rep.findings] == ["donation-miss"]
    assert not rep.ok


# ---------------------------------------------------------------------------
# commscheck: derived GSPMD model bytes vs hand-computed sizes
# ---------------------------------------------------------------------------

def test_derived_train_comms_bytes_hand_computed():
    cfg = PRESETS["gpt2_124m"]()
    sizes = shardcheck.mesh_sizes_for("fsdp", (2, 1))
    tcfg = TrainConfig(parallelism="fsdp", batch_size=4)
    entries, findings = commscheck.derived_train_comms(
        cfg, "fsdp", sizes, tcfg, accum=2)
    assert findings == []
    total = commscheck._n_params(cfg)
    by = {e["origin"]: e for e in entries}
    # fsdp grads: reduce-scatter of fp32 grads once per micro-step
    assert by["grads"]["family"] == "reduce_scatter"
    assert by["grads"]["bytes"] == total * 4 * 2
    # fsdp param gathers: bf16 params per micro-step (overlap=auto does
    # not hoist them out of the accumulation scan)
    act = jnp.dtype(tcfg.compute_dtype).itemsize
    assert by["param-gather"]["family"] == "all_gather"
    assert by["param-gather"]["bytes"] == total * act * 2
    assert by["param-gather"]["hoisted"] is False


def test_derived_sp_ring_matches_traced_ppermute_bytes():
    """The derived sp-ring formula must price the ring EXACTLY like the
    jaxpr says: per-step ppermute bytes at sp/4x2 match the traced
    zig-zag ring's scan-weighted inventory."""
    [r] = commscheck.check_cells(["train/gpt2_124m/sp/4x2"])
    assert r.traced and r.ok
    ring = [c for c in r.collectives if c["family"] == "ppermute"]
    derived = [d for d in r.derived if d["origin"] == "sp-ring"]
    assert len(ring) == 1 and len(derived) == 1
    assert ring[0]["bytes"] == derived[0]["bytes"]


def test_derived_pipe_1f1b_entry_hand_computed():
    """pp at 4x2 (pipe=2) under the auto schedule prices the interleaved
    hand-backs: S=2, vpp=n_layer/S=6, M=auto(min(B, 2S))=4 gives 25 fwd
    ticks ((M-1 over S rounds) x 12 chunks + drain) — each tick rolls one
    microbatch's activations, fwd + mirrored bwd."""
    cfg = PRESETS["gpt2_124m"]()
    sizes = shardcheck.mesh_sizes_for("pp", (4, 2))
    tcfg = TrainConfig(parallelism="pp", batch_size=4)
    entries, findings = commscheck.derived_train_comms(
        cfg, "pp", sizes, tcfg, accum=2)
    assert findings == []
    by = {e["origin"]: e for e in entries}
    assert "pipe-boundary" not in by
    e = by["pipe-1f1b"]
    assert e["family"] == "ppermute" and e["axis"] == "pipe"
    assert e["vpp"] == 6 and e["n_microbatches"] == 4
    assert e["ticks"] == 2 * 25
    act = jnp.dtype(tcfg.compute_dtype).itemsize
    tok_bytes = 1 * cfg.block_size * cfg.n_embd * act  # b_loc = 4/4
    assert e["bytes"] == 2 * 25 * 2 * tok_bytes // 4


def test_derived_pipe_carry_entry_when_schedule_forced():
    """pp_schedule='carry' keeps the round-15 boundary pricing: each of
    the pipe-1 stage boundaries crossed once per direction per
    micro-step with the full local batch."""
    import dataclasses
    cfg = dataclasses.replace(PRESETS["gpt2_124m"](), pp_schedule="carry")
    sizes = shardcheck.mesh_sizes_for("pp", (4, 2))
    tcfg = TrainConfig(parallelism="pp", batch_size=4)
    entries, _ = commscheck.derived_train_comms(
        cfg, "pp", sizes, tcfg, accum=2)
    by = {e["origin"]: e for e in entries}
    assert "pipe-1f1b" not in by
    act = jnp.dtype(tcfg.compute_dtype).itemsize
    tok_bytes = 1 * cfg.block_size * cfg.n_embd * act
    assert by["pipe-boundary"]["bytes"] == 2 * (2 - 1) * 2 * tok_bytes


def test_offload_cell_host_update_donation_all_consumed():
    """The offload audit cell: the traced host optax update must donate
    params + opt_state with every donated leaf consumed (in-place moment
    update in host RAM), zero collectives in the host program, and the
    derived model must carry both PCIe host-transfer legs at 4P bytes."""
    [r] = commscheck.check_cells(["train/gpt2_124m/fsdp/2x1/offload"])
    assert r.traced and r.ok, "\n".join(str(f) for f in r.findings)
    don = r.donation["host_update"]
    assert don["donated"] > 0
    assert don["missed"] == [] and don["donated"] == don["consumed"]
    p4 = commscheck._n_params(PRESETS["gpt2_124m"]()) * 4
    host = {e["origin"]: e for e in r.derived
            if e["family"] == "host_transfer"}
    assert host["offload-grads"]["direction"] == "to_host"
    assert host["offload-params"]["direction"] == "to_device"
    assert host["offload-grads"]["bytes"] == p4
    assert host["offload-params"]["bytes"] == p4


def test_7b_preset_validates_on_the_pod_rung_meshes():
    """The gpt2_7b preset's spec tables stay green on the pod-rung cells
    it ships on — pp (pipe=2), fsdp, fsdp_tp at 4x2 — and on the
    supervisor's rung-down re-mesh shape (data 4->2, elastic restart
    after a host loss)."""
    cfg = PRESETS["gpt2_7b"]()
    for recipe in ("pp", "fsdp", "fsdp_tp"):
        r = shardcheck.check_config(
            cfg, recipe, shardcheck.mesh_sizes_for(recipe, (4, 2)),
            preset="gpt2_7b")
        assert r.ok, shardcheck.format_report(r)
        if recipe == "pp":
            assert r.mesh["pipe"] == 2
    down = shardcheck.check_config(
        cfg, "pp", shardcheck.mesh_sizes_for("pp", (2, 1)),
        preset="gpt2_7b", variant="rung_down:4->2")
    assert down.ok, shardcheck.format_report(down)


def test_mutation_replicated_grads_flag_promised_reduce_scatter(
        monkeypatch):
    """Seeded mutation: a grads table that silently replicates under a
    sharded-grad recipe must raise promised-reduce-scatter (the silent
    all-reduce regression)."""
    monkeypatch.setattr(
        shd, "grads_pspecs",
        lambda shapes, specs, recipe, mesh: jax.tree_util.tree_map(
            lambda s: P(), specs, is_leaf=lambda x: isinstance(x, P)))
    cfg = PRESETS["gpt2_124m"]()
    sizes = shardcheck.mesh_sizes_for("fsdp", (2, 1))
    tcfg = TrainConfig(parallelism="fsdp", batch_size=4)
    entries, findings = commscheck.derived_train_comms(
        cfg, "fsdp", sizes, tcfg, accum=2)
    assert any(f.rule == "promised-reduce-scatter" and
               f.severity == "error" for f in findings)
    by = {e["origin"]: e for e in entries}
    assert by["grads"]["family"] == "all_reduce"  # the degraded class


# ---------------------------------------------------------------------------
# commscheck: trace-signature enumeration vs retrace budgets
# ---------------------------------------------------------------------------

def test_decode_signatures_within_budget_both_modes():
    wave = commscheck.check_cells(
        ["decode/gpt2_124m/single/1x1/wave"], trace_mode="off")[0]
    chunked = commscheck.check_cells(
        ["decode/gpt2_124m/single/1x1/chunked"], trace_mode="off")[0]
    assert wave.ok and chunked.ok
    ws = wave.signatures["enumerated"]
    assert ws["fused_step"] == 0 and ws["admit"] == len(ws["buckets"])
    assert ws["buckets"] == sorted(set(ws["buckets"]))  # distinct, sorted
    assert ws["spec_step"] == 1             # round-20 verify program
    cs = chunked.signatures["enumerated"]
    assert cs == {"step": 1, "fused_step": 1, "admit": 0, "spec_step": 1,
                  "promote": 1, "buckets": []}  # round-22: promote is
    # part of the static universe so the AOT store's warm walk covers it


def test_mutation_bucketing_bug_fails_signature_enumeration(monkeypatch):
    """Seeded mutation: an identity 'bucketing' that compiles one program
    per prompt length must fail the closed-form vs brute-force
    cross-check at lint time."""
    from distributed_pytorch_tpu.engine import decode as eng
    monkeypatch.setattr(eng, "prefill_bucket_for",
                        lambda n, mb, bs, ml: min(max(n, mb), ml))
    [r] = commscheck.check_cells(["decode/gpt2_124m/single/1x1/wave"],
                                 trace_mode="off")
    assert any(f.rule == "signature-enumeration" for f in r.findings)
    assert not r.ok


def test_mutation_extra_trace_signature_breaks_budget(monkeypatch):
    """Seeded mutation: a third step signature exceeds the TraceGuard
    budget of 1 — rule trace-budget."""
    from distributed_pytorch_tpu.engine import decode as eng
    real = eng.enumerate_trace_signatures

    def seeded(**kw):
        sigs = dict(real(**kw))
        sigs["step"] = 3
        return sigs

    monkeypatch.setattr(eng, "enumerate_trace_signatures", seeded)
    [r] = commscheck.check_cells(["decode/gpt2_124m/single/1x1/chunked"],
                                 trace_mode="off")
    assert any(f.rule == "trace-budget" and f.path == "step"
               for f in r.findings)
    assert not r.ok


# ---------------------------------------------------------------------------
# commscheck: golden round trip + seeded divergence
# ---------------------------------------------------------------------------

def _cell_diffs(golden, report):
    diffs = []
    commscheck._diff_value(report.key, golden["reports"][report.key],
                           report.to_dict(), diffs)
    return diffs


def test_commscheck_golden_round_trip():
    """Re-auditing golden cells reproduces the committed matrix byte for
    byte: same collectives, bytes, donation, signatures, findings."""
    golden = commscheck.load_golden()
    assert golden is not None and golden["ok"]
    for key in ("train/gpt2_124m/fsdp/2x1",
                "decode/gpt2_124m/single/1x1/chunked"):
        [r] = commscheck.check_cells([key])
        assert r.traced
        assert _cell_diffs(golden, r) == []


def test_mutation_extra_psum_diverges_from_golden(monkeypatch):
    """Seeded mutation: one extra collective in the traced step shows up
    as a golden diff — the refactor-gate property."""
    real = commscheck.collective_inventory

    def seeded(jaxpr):
        inv = real(jaxpr)
        inv.append({"family": "all_reduce", "prim": "psum",
                    "axes": ["data"], "count": 1, "bytes": 4096})
        return inv

    monkeypatch.setattr(commscheck, "collective_inventory", seeded)
    golden = commscheck.load_golden()
    [r] = commscheck.check_cells(["train/gpt2_124m/fsdp/2x1"])
    diffs = _cell_diffs(golden, r)
    assert diffs and any("collectives" in d for d in diffs)


def test_mutation_dropped_donation_diverges_and_errors(monkeypatch):
    """Seeded mutation: a donation miss both fails the cell (error
    finding) and diverges from the golden donation table."""
    real = commscheck.donation_report

    def seeded(traced):
        don = real(traced)
        if don["donated"]:
            don["consumed"] -= 1
            don["n_missed"] += 1
            don["missed"] = [{"shape": [1], "dtype": "float32"}]
        return don

    monkeypatch.setattr(commscheck, "donation_report", seeded)
    golden = commscheck.load_golden()
    [r] = commscheck.check_cells(["train/gpt2_124m/fsdp/2x1"])
    assert any(f.rule == "donation-miss" for f in r.findings)
    assert not r.ok
    diffs = _cell_diffs(golden, r)
    assert any("donation" in d for d in diffs)


def test_diff_golden_trace_mode_mismatch_short_circuits():
    payload = {"trace_mode": "off", "reports": {}}
    golden = {"trace_mode": "auto", "reports": {}}
    diffs = commscheck.diff_golden(payload, golden)
    assert len(diffs) == 1 and "trace_mode" in diffs[0]


def test_golden_covers_shardcheck_matrix_plus_engine_cells():
    """The committed golden must stay in lockstep with the audit scope:
    every train cell of the base matrix, the overlap A/B pair, and the
    four engine cells."""
    golden = commscheck.load_golden()
    keys = set(golden["reports"])
    assert "train/gpt2_124m/fsdp/2x1/overlap-accum1" in keys
    assert "train/gpt2_124m/fsdp/2x1/overlap-accum2" in keys
    decode = {k for k in keys if k.startswith("decode/")}
    assert len(decode) == len(commscheck.DECODE_CELLS)
    assert "train/gpt2_124m/fsdp/2x1/offload" in keys
    # 6 configs x (9 recipes x 3 meshes + single@1x1) + 2 overlap +
    # 1 offload + 4 engine cells
    assert len(keys) == 6 * (9 * 3 + 1) + 2 + 1 + 4
    assert golden["errors"] == 0 and golden["ok"]


# ---------------------------------------------------------------------------
# the package graph: every import inside the program points down
# ---------------------------------------------------------------------------

#: lowest first; packages of one tier are not ordered among themselves
LAYERS = (("obs", "data"), ("parallel",), ("ops",), ("models",),
          ("train", "engine"), ("serve",))
_TIER = {pkg: i for i, tier in enumerate(LAYERS) for pkg in tier}
#: the tools that audit or store the WHOLE program from below it: they
#: build the model, the train step and the engine to read their programs.
#: By file name; a file that stops needing its place here must leave it.
UPWARD_TOOLS = {"parallel/shardcheck.py", "parallel/commscheck.py",
                "parallel/aot_store.py"}


def _imported_packages(node, pkg):
    """Sub-packages of distributed_pytorch_tpu an import statement names,
    lazy imports and relative ones (resolved from `pkg`) included."""
    top = "distributed_pytorch_tpu"
    if isinstance(node, ast.Import):
        names = [a.name for a in node.names]
    elif isinstance(node, ast.ImportFrom):
        base = node.module or ""
        if node.level:      # a module of `pkg`: level 1 is pkg, 2 the root
            prefix = [top, pkg][:3 - node.level]
            base = ".".join(prefix + ([base] if base else []))
        # `from distributed_pytorch_tpu import ops` names one too
        names = [base] + [f"{base}.{a.name}" for a in node.names]
    else:
        return set()
    parts = (n.split(".") for n in names)
    return {p[1] for p in parts if len(p) > 1 and p[0] == top} & set(_TIER)


def _upward_imports(pkg):
    """{file: [what points up]} over every module of the package."""
    root = REPO / "distributed_pytorch_tpu"
    up = {}
    for path in sorted((root / pkg).rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            for target in sorted(_imported_packages(node, pkg)):
                if _TIER[target] > _TIER[pkg]:
                    up.setdefault(str(path.relative_to(root)), []).append(
                        f"line {node.lineno} imports {target}")
    return up


@pytest.mark.parametrize("pkg", list(_TIER))
def test_no_import_points_up_the_layers(pkg):
    up = _upward_imports(pkg)
    offenders = {f: v for f, v in up.items() if f not in UPWARD_TOOLS}
    assert not offenders, (
        f"{pkg} sits below what it imports (order {LAYERS}): move the "
        f"shared piece down, do not add to UPWARD_TOOLS: {offenders}")
    stale = {f for f in UPWARD_TOOLS if f.startswith(pkg + "/")} - set(up)
    assert not stale, f"no upward import left, take out of UPWARD_TOOLS: " \
                      f"{stale}"


# ---------------------------------------------------------------------------
# what the engine counts is named and booked in engine/counts.py alone
# ---------------------------------------------------------------------------

def _count_names():
    """(the table's attribute names, every total and derived reading)."""
    from distributed_pytorch_tpu.engine import counts
    held = counts.EngineCounts(config.LLMConfig(), [], 1, 0)
    mine = {n for n in vars(held) if not n.startswith("_")} | {
        n for n, v in vars(counts.EngineCounts).items()
        if isinstance(v, property)}
    table = {r.name for r in counts.READINGS}
    assert table <= mine and len(table) == len(counts.READINGS)
    return table, mine


@pytest.mark.parametrize("reader", ["serve/scheduler.py", "serve/server.py",
                                    "engine/decode.py"])
def test_a_counter_is_named_in_the_table_and_booked_in_counts(reader):
    """The readers walk the table: not one of its names stands in their
    text, comments and docstrings included (a gauge's name may hold one:
    `serve_engine_overlap_share`). The engine reads counts and calls the
    booking methods; it assigns to none, on itself or on `counts`, and
    defines none (either would shadow the forwarded name)."""
    import re
    table, mine = _count_names()
    path = REPO / "distributed_pytorch_tpu" / reader
    text = path.read_text()
    if reader.startswith("serve/"):
        named = {n for n in table if re.search(rf"\b{n}\b", text)}
        assert not named, f"{reader} names a counter of the table: {named}"
        assert "counts." in text
        return
    stores, defined = [], []
    for node in ast.walk(ast.parse(text)):
        if isinstance(node, (ast.FunctionDef, ast.ClassDef)) \
                and node.name in mine:
            defined.append(node.name)
        targets = node.targets if isinstance(node, ast.Assign) else \
            [node.target] if isinstance(node, (ast.AugAssign,
                                               ast.AnnAssign)) else []
        for target in targets:
            for sub in ast.walk(target):
                if isinstance(sub, ast.Attribute) and sub.attr in mine:
                    stores.append(f"line {node.lineno}: {sub.attr}")
    assert not stores and not defined, (stores, defined)
    assert text.count("EngineCounts(") == 1 \
        and text.count("self.counts.drained(") == 1 \
        and text.count(".record_turn(") == 1 and "**record)" in text
