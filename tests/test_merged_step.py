"""A chunk-carrying program of a patterned model walks the layers once with
two row sets (the chunk's rows, the decode rows) and its expert layers make
ONE call over both (`engine/decode.py make_fused_step_fn`, `models/gpt.py
Rows`): against the two `model.apply` calls such a program used to make,
written out here; one call a layer in the counts and in the program; pads
and dead slots routed nowhere; the tile rule at the merged call's rows; the
counter that says which program an engine runs. Both routers: the sigmoid
top-k over relu^2 experts (tests/test_hybrid.py's fixture) and the softmax
top-k over gated ones (tests/test_granite.py's)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.engine.decode import make_fused_step_fn
from distributed_pytorch_tpu.models.gpt import LLM, Rows
from distributed_pytorch_tpu.ops import grouped_matmul as gm

from test_granite import LLM_KW as GRANITE_KW
from test_hybrid import LLM_KW as HYBRID_KW

HI = jax.default_matmul_precision("highest")
N_SLOTS, CHUNK = 3, 16


@pytest.fixture(scope="module", params=["sigmoid_relu2", "softmax_gated"])
def mv(request):
    kw = HYBRID_KW if request.param == "sigmoid_relu2" else GRANITE_KW
    cfg = LLMConfig(**kw)
    model = LLM(cfg, compute_dtype=jnp.float32, attn_impl="naive")
    variables = model.init({"params": jax.random.PRNGKey(1)},
                           jnp.zeros((1, 8), jnp.int32))
    # weights a few times the N(0, 0.02) draw: every term moves the logits
    variables = jax.tree_util.tree_map(
        lambda a: a * 6.0 if a.ndim >= 2 else a, variables)
    return cfg, model, variables


def _engine(model, variables, **kw):
    kw = {"n_slots": N_SLOTS, "max_len": 128, "block_size": 8,
          "prefill_chunk": CHUNK, "temperature": 0.0, "min_bucket": 8,
          "prefix_cache": False, **kw}
    return DecodeEngine(model, variables, **kw)


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).tolist() for n in lens]


def _n_expert_layers(cfg):
    return cfg.layer_pattern.count("E")


def _two_pass(model, sample_fn, n_slots, W):
    """The fused step as it was: the model run twice over the same
    variables, the chunk of one parked slot and then one token of every
    live slot, the cache tree flowing from the first to the second. Also
    hands out both sets of logits."""

    def fused_step(variables, caches, tok, pos, live, bt, rng, t, qparams,
                   ctoks, cslot, coff, clen, cdone):
        bt_row = jax.lax.dynamic_slice(bt, (cslot, jnp.int32(0)), (1, W))
        clogits, _, caches = model.apply(
            variables, ctoks, None, caches, coff, deterministic=True,
            logits_idx=clen - 1, block_tables=bt_row,
            state_ctx={"slot": cslot, "valid_len": clen})
        first = sample_fn(clogits[:, -1, :],
                          jax.random.fold_in(rng, 2 ** 21 + t))
        logits, _, caches = model.apply(
            variables, tok[:, None], None, caches, pos, deterministic=True,
            block_tables=bt, state_ctx={"live": live})
        nxt = sample_fn(logits[:, -1, :], jax.random.fold_in(rng, t))
        nxt = jnp.where(live, nxt, tok)
        pos = pos + live.astype(jnp.int32)
        sel = (jnp.arange(n_slots) == cslot) & cdone
        nxt = jnp.where(sel, first[0], nxt)
        pos = jnp.where(sel, coff + clen[0], pos)
        return (caches, nxt, pos, jnp.logical_or(live, sel)), \
            (clogits, logits)

    return fused_step


def _one_walk_logits(model, W):
    """The logits of the one walk over both row sets, as the engine's
    program asks for them."""

    def logits(variables, caches, tok, pos, live, bt, rng, t, qparams,
               ctoks, cslot, coff, clen, cdone):
        bt_row = jax.lax.dynamic_slice(bt, (cslot, jnp.int32(0)), (1, W))
        out, _, _ = model.apply(
            variables,
            (Rows(ctoks, coff, bt_row, {"slot": cslot, "valid_len": clen},
                  clen - 1, scope="chunk_prefill"),
             Rows(tok[:, None], pos, bt, {"live": live})),
            None, caches, deterministic=True)
        return out

    return logits


# (a) the merged program against the two passes ------------------------------

def test_the_one_walk_gives_what_the_two_passes_gave(mv):
    """Every chunk-carrying program of a run (five prompts through three
    slots: first chunks that reset a slot's state, later ones that carry
    it, chunks shorter than their rows, dead slots beside them) is also run
    as two passes on the same inputs: the same sampled tokens, positions
    and live mask, caches and both sets of logits to float32 rounding, and
    the one row of routing counts the sum of the two passes' rows."""
    cfg, model, variables = mv
    eng = _engine(model, variables)
    merged = eng._get_fused_step_fn()
    two = jax.jit(_two_pass(model, eng._sample, N_SLOTS, eng.table_width))
    walk = jax.jit(_one_walk_logits(model, eng.table_width))
    seen = []

    def both(*args):
        out = merged(*args)
        (caches2, *rest2), logits2 = two(*args)
        live_in, coff, clen = args[4], int(args[11]), int(args[12][0])
        for a, b in zip(out[1:], rest2):
            np.testing.assert_array_equal(a, b)
        for got, want in zip(walk(*args), logits2):
            np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
        for kind, c1, c2 in zip(cfg.layer_pattern, out[0], caches2):
            if kind != "E":
                jax.tree_util.tree_map(
                    lambda a, b: np.testing.assert_allclose(
                        a, b, atol=2e-5, rtol=2e-5), c1, c2)
                continue
            assert c1["tokens"].shape == (1, 4)
            assert c2["tokens"].shape == (2, 4)
            np.testing.assert_array_equal(c1["tokens"][0],
                                          c2["tokens"].sum(axis=0))
            assert int(c1["absent"][0]) == int(c2["absent"].sum())
            if "held_gate" in c1:
                np.testing.assert_allclose(
                    c1["held_gate"][0], c2["held_gate"].sum(), rtol=1e-5)
            # (c) pads and dead slots are sent to no routed expert
            real = clen + int(np.asarray(live_in).sum())
            assert int(c1["tokens"].sum()) + int(c1["absent"][0]) \
                == real * cfg.n_act_routed
        seen.append((coff, clen, int(np.asarray(live_in).sum())))
        return out

    eng._fused_step_fn = both
    with HI:
        eng.run(_prompts((37, 9, 20, 50, 5)), 6)
    assert any(coff == 0 for coff, _, _ in seen)          # a state reset
    assert any(coff > 0 for coff, _, _ in seen)           # a carried state
    assert any(clen < CHUNK for _, clen, _ in seen)       # pad rows
    assert any(live < N_SLOTS - 1 for _, _, live in seen)  # dead slots
    assert any(live > 0 for _, _, live in seen)
    assert eng.merged_programs == eng.chunk_programs == len(seen)


# (b) one call a layer --------------------------------------------------------

def _expert_kernel_calls(fn, args) -> dict:
    """Calls of each expert kernel in a program, by the kernel's name."""
    counts: dict = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                counts[name] = counts.get(name, 0) + 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return {k: v for k, v in counts.items() if "expert_matmul" in k}


def test_a_fused_program_holds_each_expert_kernel_once_a_layer(mv):
    cfg, model, variables = mv
    eng = _engine(model, variables)
    args = (eng.variables, eng.caches, eng.tok, eng.pos, eng.live,
            eng.block_tables, eng._rng, jnp.int32(0), None,
            jnp.zeros((1, CHUNK), jnp.int32), jnp.int32(0), jnp.int32(0),
            jnp.asarray([4], jnp.int32), jnp.bool_(True))
    n_e = _n_expert_layers(cfg)
    up = "expert_matmul_gated_up" if cfg.router == "softmax_topk" \
        else "expert_matmul_up"
    merged = make_fused_step_fn(model, eng._sample, N_SLOTS,
                                eng.table_width)
    assert _expert_kernel_calls(merged, args) \
        == {up: n_e, "expert_matmul_down": n_e}
    two = _two_pass(model, eng._sample, N_SLOTS, eng.table_width)
    assert _expert_kernel_calls(two, args) \
        == {up: 2 * n_e, "expert_matmul_down": 2 * n_e}
    caches = merged(*args)[0]
    for kind, c in zip(cfg.layer_pattern, caches):
        if kind == "E":
            assert all(leaf.shape[0] == 1 for leaf in c.values()), c


def test_expert_calls_grow_by_the_expert_layers_a_program(mv):
    """A program of either kind makes one call an expert layer: the
    engine's `expert_calls` counts the calls the kernels made (the
    benchmark divides the experts hit and the second tiles by it), and the
    flight record carries them a program."""
    cfg, model, variables = mv
    eng = _engine(model, variables)
    with HI:
        eng.run(_prompts((37, 9, 20)), 5)
    n_e = _n_expert_layers(cfg)
    recs = eng.flight.entries()
    assert {r["expert_calls"] for r in recs} == {n_e}
    assert any(r["prefill_tokens"] for r in recs)
    assert not all(r["prefill_tokens"] for r in recs)
    assert eng.expert_calls == n_e * len(recs)
    if cfg.router == "softmax_topk":
        # a merged call is booked under `chunk`, a plain program's under
        # `decode`
        assert eng.expert_calls_by == {
            "chunk": n_e * eng.chunk_programs,
            "decode": n_e * (len(recs) - eng.chunk_programs)}


# (c) on the layer itself: rows that are not real are routed nowhere ---------

def test_pads_and_dead_slots_are_routed_nowhere_in_the_merged_call(mv):
    """One walk over a chunk of 5 real rows of 16 and three slots of which
    one is live: 6 real rows, and the expert layers' counts say so."""
    cfg, model, variables = mv
    eng = _engine(model, variables)
    live = jnp.asarray([False, True, False])
    with HI:
        _, _, caches = model.apply(
            variables,
            (Rows(jnp.full((1, CHUNK), 7, jnp.int32), jnp.int32(0),
                  eng.block_tables[:1],
                  {"slot": jnp.int32(0),
                   "valid_len": jnp.asarray([5], jnp.int32)},
                  jnp.asarray([4], jnp.int32), scope="chunk_prefill"),
             Rows(jnp.full((N_SLOTS, 1), 9, jnp.int32),
                  jnp.zeros((N_SLOTS,), jnp.int32), eng.block_tables,
                  {"live": live})),
            None, eng.caches, deterministic=True)
    for kind, c in zip(cfg.layer_pattern, caches):
        if kind == "E":
            assert int(c["tokens"].sum()) + int(c["absent"][0]) \
                == (5 + 1) * cfg.n_act_routed


def test_the_layer_gives_each_row_set_what_it_gets_alone(mv):
    """`RoutedExperts` over two row sets against a call a set: the same
    rows out (the router and the shared expert run a set at a time, the
    held experts once over all), one row of counts = the two calls' sum."""
    from distributed_pytorch_tpu.models.mlp import RoutedExperts
    cfg, _, variables = mv
    layer = RoutedExperts(cfg)
    params = {"params": variables["params"]["block_1"]["moe"]}
    xa = jax.random.normal(jax.random.PRNGKey(2), (1, CHUNK, cfg.n_embd))
    xb = jax.random.normal(jax.random.PRNGKey(3), (N_SLOTS, 1, cfg.n_embd))
    ma = jnp.arange(CHUNK) < 11
    mb = jnp.asarray([True, False, True])
    with HI:
        (ya, yb), stats = layer.apply(params, [xa, xb], [ma, mb])
        ya1, sa = layer.apply(params, xa, ma)
        yb1, sb = layer.apply(params, xb, mb)
    np.testing.assert_array_equal(ya, ya1)
    np.testing.assert_array_equal(yb, yb1)
    assert ya.shape == xa.shape and yb.shape == xb.shape
    np.testing.assert_array_equal(stats["tokens"],
                                  sa["tokens"] + sb["tokens"])
    assert int(stats["absent"][0]) == int(sa["absent"][0] + sb["absent"][0])


# (c') the combine: a token gathers its experts' rows ------------------------

@pytest.fixture(scope="module", params=["relu2_64of128_top6",
                                        "gated_36of72_top10"])
def combined(request):
    """`held_experts_ffn` over 256 + 64 rows at the two cells' routing
    (narrow widths, float32, interpret mode), beside the call over each set
    alone and the kernels' own packed result `y` of the merged call. Among
    the rows: masked ones (ids -1), tokens none of whose experts is held,
    and absent experts in nearly every other row."""
    gated = request.param.startswith("gated")
    held, n_routed, k, first = (36, 72, 10, 0) if gated else (64, 128, 6, 64)
    N, C, F, cuts = 320, 128, 64, (256, 64)
    ks = jax.random.split(jax.random.PRNGKey(k), 5)
    x = jax.random.normal(ks[0], (N, C))
    w_up = jax.random.normal(ks[1], (held, (2 if gated else 1) * F, C)) * 0.1
    w_down = jax.random.normal(ks[2], (held, F, C)) * 0.1
    idx = jnp.argsort(jax.random.uniform(ks[3], (N, n_routed)),
                      axis=1)[:, :k].astype(jnp.int32)
    absent = (jnp.arange(n_routed - held)
              + jnp.where(first, 0, held))[:k].astype(jnp.int32)
    idx = idx.at[3::16].set(absent)                   # nothing held
    idx = idx.at[5::8].set(-1)                        # pads, dead slots
    gates = jax.random.uniform(ks[4], (N, k), minval=0.1)
    kw = dict(first=first, n_routed=n_routed, gated=gated, interpret=True)

    packed, down = [], gm._held_down_call

    def keep(*a, **b):
        packed.append(down(*a, **b))
        return packed[-1]

    with pytest.MonkeyPatch.context() as mp, HI:
        mp.setattr(gm, "_held_down_call", keep)
        sets, _ = gm.held_experts_ffn(x, idx, gates, w_up, w_down,
                                      cuts=cuts, **kw)
        alone, at = [], 0
        for n in cuts:
            alone.append(gm.held_experts_ffn(
                x[at:at + n], idx[at:at + n], gates[at:at + n], w_up,
                w_down, **kw)[0])
            at += n
    local = np.asarray(idx) - first
    return dict(sets=[np.asarray(a) for a in sets],
                alone=[np.asarray(a) for a in alone], y=np.asarray(packed[0]),
                local=np.where((local >= 0) & (local < held), local, held),
                held=held, tile=gm.held_tile_rows(N, k, n_routed))


def test_a_row_set_beside_another_reads_what_it_reads_alone(combined):
    """Bit for bit, float32: a row's sum is made of its own k rows of the
    kernels' result, whatever rows the call holds beside it."""
    for beside, alone in zip(combined["sets"], combined["alone"]):
        assert beside.dtype == np.float32
        np.testing.assert_array_equal(beside, alone)


def test_a_row_is_the_sum_of_its_experts_rows_in_the_routers_order(combined):
    """Against the kernels' own packed result: experts ascending, each
    expert's rows in assignment order from a tile boundary; a token's row
    is ((y[s_0] + y[s_1]) + ...) over its held assignments as the router
    returned them, in float32, bit for bit."""
    local, held, bm, y = (combined[n] for n in ("local", "held", "tile", "y"))
    counts = np.bincount(local.reshape(-1), minlength=held + 1)[:held]
    start = np.concatenate([[0], np.cumsum(-(-counts // bm) * bm)])
    seen = np.zeros(held + 1, int)
    want = np.zeros((local.shape[0], y.shape[1]), np.float32)
    for t, row in enumerate(local):
        terms = []
        for e in row:
            terms.append(np.zeros_like(y[0]) if e == held
                         else y[start[e] + seen[e]])
            seen[e] += 1
        want[t] = functools.reduce(np.add, terms)
    np.testing.assert_array_equal(np.concatenate(combined["sets"]), want)
    assert np.abs(want).max() > 0.1


def test_a_token_with_no_held_expert_gets_exactly_zero(combined):
    out = np.concatenate(combined["sets"])
    nothing = (combined["local"] == combined["held"]).all(axis=1)
    assert nothing.sum() >= 40 + 20 and not out[nothing].any()
    assert out[~nothing].any(axis=1).all()


# (c'') the packing, against a counting sort written out ---------------------

def _plain_packing(idx, gates, first, n_held, bm):
    """What `gm.held_packing` returns, the plain way: a stable counting
    sort of the flattened assignments by held expert, each expert's rows
    from a tile boundary; the tile table beside it."""
    N, k = idx.shape
    n_tiles = -(-N * k // bm) + n_held
    P = n_tiles * bm
    local = idx.reshape(-1) - first
    row_tok = np.zeros(P, np.int32)
    row_gate = np.zeros((P, 1), np.float32)
    slot_of = np.full(N * k, P, np.int32)
    tiles, at = [], 0
    for g in range(n_held):
        mine = np.flatnonzero(local == g)           # in assignment order
        row_tok[at:at + mine.size] = mine // k
        row_gate[at:at + mine.size, 0] = gates.reshape(-1)[mine]
        slot_of[mine] = at + np.arange(mine.size)
        tiles += [g] * -(-mine.size // bm)
        at = len(tiles) * bm
    # an unused tile repeats the last used one's expert (none: the last)
    last = tiles[-1] if tiles else n_held - 1
    group = np.array(tiles + [last] * (n_tiles - len(tiles)), np.int32)
    return (row_tok, row_gate, group, np.array([len(tiles)], np.int32),
            slot_of.reshape(N, k))


# the four MoE cells' plain and chunk-carrying calls: rows, top k, router
# width, experts held (ISSUE 58's table)
PACKED_CALLS = {
    "nemotron_64": (64, 6, 128, 64), "nemotron_320": (320, 6, 128, 64),
    "granite_64": (64, 10, 72, 36), "granite_320": (320, 10, 72, 36),
    "lfm2_128": (128, 4, 64, 64), "lfm2_384": (384, 4, 64, 64),
    "laguna_64": (64, 10, 256, 32), "laguna_1088": (1088, 10, 256, 32),
}
PACKED_FIRST = 5           # held: ids 5 .. 5 + n_held - 1


def _routing(call, kind):
    """(N, k) ids and gates. `mixed`: a router's distinct top k a row, with
    masked rows (-1), absent experts on both sides of the held range, one
    held expert nobody picks and one that three rows in four pick (more
    than a tile); `none`: every assignment absent or masked; `repeats`:
    ids drawn with replacement, so a row names an expert twice."""
    N, k, n_routed, n_held = PACKED_CALLS[call]
    rng = np.random.default_rng(N * k + n_held)
    gates = rng.random((N, k), dtype=np.float32) + 0.1
    if kind == "repeats":
        return rng.integers(-1, n_routed, (N, k)).astype(np.int32), gates
    empty, crowded = PACKED_FIRST + 2, PACKED_FIRST + 1
    idx = np.argsort(rng.random((N, n_routed - 1)), axis=1)[:, :k]
    idx = (idx + (idx >= empty)).astype(np.int32)   # nobody picks `empty`
    for t in range(3 * N // 4):
        if crowded not in idx[t]:
            idx[t, t % k] = crowded
    idx[rng.random(N) < 0.1] = -1
    if kind == "none":
        idx = np.where((idx >= PACKED_FIRST)
                       & (idx < PACKED_FIRST + n_held), -1, idx)
    return idx, gates


@pytest.mark.parametrize("kind", ["mixed", "none", "repeats"])
@pytest.mark.parametrize("call", list(PACKED_CALLS))
def test_the_packing_is_the_counting_sort_slot_for_slot(call, kind):
    """`row_tok`, `row_gate`, `group`, `n_used` and `slot_of`, array_equal:
    the dense ops place every assignment where the stable sort placed it
    (PR 58), at the eight call shapes of the cells."""
    N, k, n_routed, n_held = PACKED_CALLS[call]
    bm = gm.held_tile_rows(N, k, n_routed)
    idx, gates = _routing(call, kind)
    want = _plain_packing(idx, gates, PACKED_FIRST, n_held, bm)
    got = jax.jit(functools.partial(gm.held_packing, first=PACKED_FIRST,
                                    n_held=n_held, bm=bm))(idx, gates)
    for name, g, w in zip(("row_tok", "row_gate", "group", "n_used",
                           "slot_of"), got, want):
        g = np.asarray(g)
        assert (g.shape, g.dtype) == (w.shape, w.dtype), name
        np.testing.assert_array_equal(g, w, err_msg=name)
    local = idx - PACKED_FIRST
    counts = np.bincount(local[(local >= 0) & (local < n_held)],
                         minlength=n_held)
    if kind == "mixed":     # the routing holds what the case is about
        assert counts.max() > bm and counts[2] == 0 and (idx == -1).any()
        assert ((idx >= 0) & (idx < PACKED_FIRST)).any()
        assert (idx >= PACKED_FIRST + n_held).any() or n_held == n_routed
    if kind == "none":
        assert not counts.any() and int(got[3][0]) == 0
        assert (np.asarray(got[4]) == want[0].size).all()


# (d) the tile at the merged call's rows -------------------------------------

@pytest.mark.parametrize("rows, k, n_routed, tile", [
    (320, 6, 128, 32),       # nemotron_h_serve_closed64: 15 rows expected
    (320, 10, 72, 64),       # granite4h_serve_closed64: 44.4
    (64, 6, 128, 16),        # the plain programs: as they were
    (64, 10, 72, 32),
    (256, 6, 128, 32),       # a chunk alone (a wave admit, two passes)
    (256, 10, 72, 64),
    (512, 10, 72, 128),      # 71 expected: the mean alone fills 64
])
def test_tile_rows_at_the_merged_calls_rows(rows, k, n_routed, tile):
    assert gm.held_tile_rows(rows, k, n_routed) == tile


# (e) the counter that says which program an engine runs ---------------------

def _metrics(sched) -> dict:
    got = {}
    for line in sched.metrics.render_prometheus().splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.partition(" ")
            got[name] = float(value)
    return got


def test_merged_program_share_of_a_patterned_engine(mv):
    from distributed_pytorch_tpu.serve.scheduler import Scheduler
    cfg, model, variables = mv
    eng = _engine(model, variables)
    sched = Scheduler(eng, max_queue=4)
    assert eng.merged_program_share == 0.0        # nothing ran yet
    with HI:
        eng.run(_prompts((20, 9, 33)), 4)
    assert eng.chunk_programs > 0
    assert eng.merged_programs == eng.chunk_programs
    assert eng.merged_program_share == 1.0
    assert _metrics(sched)["serve_merged_program_share"] == 1.0


def test_merged_program_share_of_a_classic_engine():
    from distributed_pytorch_tpu.serve.scheduler import Scheduler
    cfg = LLMConfig(vocab_size=64, block_size=64, n_embd=32, n_layer=2,
                    n_head=2, n_kv_heads=2, attn="gqa", pos_emb="rope",
                    up_dim=64, non_linearity="gelu")
    model = LLM(cfg, compute_dtype=jnp.float32, attn_impl="naive")
    variables = model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 8), jnp.int32))
    eng = DecodeEngine(model, variables, n_slots=2, max_len=64,
                       block_size=8, prefill_chunk=16, min_bucket=8)
    sched = Scheduler(eng, max_queue=4)
    eng.run(_prompts((20, 9), seed=1), 3)
    assert eng.chunk_programs > 0 and eng.merged_programs == 0
    assert eng.merged_program_share == 0.0
    assert _metrics(sched)["serve_merged_program_share"] == 0.0
    assert "expert_calls" not in eng.flight.entries()[-1]


def test_a_quantised_patterned_engine_runs_the_model_twice(mv):
    """Its chunk runs outside the quantised store and its decode rows
    inside it: two sets of weights, nothing to share, two calls a layer."""
    cfg, model, variables = mv
    eng = _engine(model, variables, quantize_weights=True)
    with HI:
        eng.run(_prompts((20, 9)), 3)
    n_e = _n_expert_layers(cfg)
    assert eng.chunk_programs > 0 and eng.merged_programs == 0
    recs = eng.flight.entries()
    assert {r["expert_calls"] for r in recs if r["prefill_tokens"]} \
        == {2 * n_e}
    assert {r["expert_calls"] for r in recs if not r["prefill_tokens"]} \
        == {n_e}


def test_the_timeline_carries_the_share(mv):
    import json

    from distributed_pytorch_tpu.serve.server import ServeApp
    cfg, model, variables = mv
    eng = _engine(model, variables)
    with HI:
        eng.run(_prompts((20, 9)), 3)
    app = ServeApp.__new__(ServeApp)
    app.scheduler = type("S", (), {"engine": eng})()
    body = app._debug_timeline({})
    payload = json.loads(body.split(b"\r\n\r\n", 1)[1])
    assert payload["merged_program_share"] == 1.0
    assert payload["entries"][-1]["expert_calls"] == _n_expert_layers(cfg)
