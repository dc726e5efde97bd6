"""Latent attention as a patterned model's 'L' layer, at a small size on the
CPU: every width a stand-in, every RATIO of the published model kept (nope :
rope : value = 2 : 1 : 2, a q-latent and a kv-latent, ONE rotary key head
shared by all query heads), in front of a dense FFN and sigmoid-routed
experts with a shared one. (a) the model's and the engine's logits against
`benchmark/lib/reference_joyai.py` (float32, literal, no cache); (b) the
absorbed form against the literal one, the adjacent pairing against the
published transpose-then-halves; (c) each kernel in interpret mode against
its XLA twin; (d) every branch of each decline function and the paths line;
(e) the eight shares add up to the uncut layer; the tree's count."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import flops_joyai
from benchmark.lib import reference_joyai as ref
from distributed_pytorch_tpu.config import LAYER_KEEPS, LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.models.gpt import LLM, init_paged_cache
from distributed_pytorch_tpu.obs import paths
from distributed_pytorch_tpu.ops import latent_attention as la
from distributed_pytorch_tpu.ops import rope

LLM_KW = dict(
    vocab_size=256, block_size=1 << 17, n_embd=64, n_layer=6,
    layer_pattern="LFLELE", pos_emb="rope", rope_theta=32e6,
    rope_pairing="adjacent", norm_eps=1e-6, tie_head=False, attn="mla",
    n_head=8, q_latent_dim=48, kv_latent_dim=32, rope_head_dim=8,
    qk_nope_head_dim=16, v_head_dim=16, attn_bias=False,
    non_linearity="swiglu", up_dim=24, dense_up_dim=96, shared_up_dim=24,
    n_exp=17, n_shared=1, n_act=5, router="sigmoid", routed_scale=2.5)
HI = jax.default_matmul_precision("highest")
NH, LC, DN, DR, DV = 8, 32, 16, 8, 16


def _big(variables):
    """Weights a few times the draw, so that at 64 wide every term moves
    the logits by more than float32 rounding."""
    return jax.tree_util.tree_map(lambda a: a * 6.0 if a.ndim >= 2 else a,
                                  variables)


@pytest.fixture(scope="module")
def mv():
    cfg = LLMConfig(**LLM_KW)
    model = LLM(cfg, compute_dtype=jnp.float32)
    variables = _big(model.init({"params": jax.random.PRNGKey(1)},
                                jnp.zeros((1, 8), jnp.int32)))
    return cfg, model, variables


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).tolist() for n in lens]


def _rel(got, want):
    d = np.asarray(got, np.float64) - np.asarray(want, np.float64)
    return float(np.sqrt((d * d).mean() / (np.asarray(want) ** 2).mean()))


def _strip(cfg, caches):
    return [None if k == "E" else c
            for k, c in zip(cfg.layer_pattern, caches)]


# (1) the tree, the whole forward pass, what each term is worth -------------

def test_the_tree_is_the_published_one(mv):
    cfg, model, variables = mv
    p = variables["params"]
    assert LAYER_KEEPS["L"] == ("pools",) and not cfg.slot_state \
        and cfg.layers_keeping("pools") == 3
    attn = {k: v.shape for k, v in p["block_0"]["latent_attn"].items()}
    assert attn == {"W_qa": (64, 48), "q_norm": (48,),
                    "W_qb": (48, NH * (DN + DR)), "W_kva": (64, LC + DR),
                    "kv_norm": (LC,), "W_kvb": (LC, NH * (DN + DV)),
                    "W_o": (NH * DV, 64)}
    assert set(p["block_1"]) == {"norm", "mlp"} \
        and set(p["block_3"]) == {"norm", "moe"}
    n = sum(int(a.size) for a in jax.tree_util.tree_leaves(p))
    # the flops module counts the tree, the selection bias (a buffer) apart
    assert n == flops_joyai.total_params(LLM_KW) + 2 * 16
    # one pool leaf a latent layer, rows in whole tiles, no head axis
    caches = init_paged_cache(cfg, 9, 8, dtype=jnp.float32)
    assert [None if c is None else c.shape for c in caches] == [
        (9, 8, 128), None, (9, 8, 128), None, (9, 8, 128), None]
    assert la.row_lanes(512, 64) == 640 and la.row_lanes(LC, DR) == 128


def test_full_forward_matches_the_reference(mv):
    cfg, model, variables = mv
    idx = jnp.asarray(_prompts((45, 45), seed=3), jnp.int32)
    with HI:
        got, _, _ = model.apply(variables, idx, all_logits=True)
        want = ref.forward_logits(variables["params"], LLM_KW, idx)
    assert _rel(got, want) < 2e-5
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_term_left_out_fails_the_comparison(mv, fault):
    cfg, model, variables = mv
    idx = jnp.asarray(_prompts((45, 45), seed=3), jnp.int32)
    with HI:
        got, _, _ = model.apply(variables, idx, all_logits=True)
        spoilt = ref.forward_logits(variables["params"], LLM_KW, idx,
                                    faults=(fault,))
    assert _rel(got, spoilt) > 2e-3, fault


@pytest.mark.parametrize("chips", [8, 4])
def test_the_shares_add_up_to_the_uncut_layer(mv, chips):
    """`chips` chips share a layer's 16 experts: each one's part of the
    routed sum, added, is the layer with every expert held; the shared
    expert is counted once. And the program's share is the reference's."""
    cfg, model, variables = mv
    whole = variables["params"]["block_3"]["moe"]
    n = 16 // chips
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 19, 64))
    kw = dict(k=4, scale=2.5)
    from distributed_pytorch_tpu.models.mlp import RoutedExperts
    with HI:
        want = ref.experts_forward(h, whole, first=0, **kw)
        parts = 0.0
        for chip in range(chips):
            share = dict(whole,
                         experts_up=whole["experts_up"][n * chip:][:n],
                         experts_down=whole["experts_down"][n * chip:][:n])
            parts = parts + ref.experts_forward(
                h, share, first=n * chip, shared=chip == 0, **kw)
            held = dataclasses.replace(cfg, experts_held=(n * chip, n))
            got = RoutedExperts(held).apply({"params": share}, h)[0]
            alone = ref.experts_forward(h, share, first=n * chip, **kw)
            assert _rel(got, alone) < 2e-5
    assert _rel(parts, want) < 1e-5


# (2) the two forms, the two pairings ----------------------------------------

def _operands(key, B, T, S, dtype=jnp.float32):
    k = jax.random.split(key, 5)
    q_nope = jax.random.normal(k[0], (B, T, NH, DN), dtype)
    q_rope = jax.random.normal(k[1], (B, T, NH, DR), dtype)
    rows = la.cache_rows(jax.random.normal(k[2], (B, S, LC), dtype),
                         jax.random.normal(k[3], (B, S, DR), dtype),
                         la.row_lanes(LC, DR))
    w = jax.random.normal(k[4], (LC, NH, DN + DV), dtype) * 0.3
    return q_nope, q_rope, rows, w


@pytest.mark.parametrize("lens", [(40, 40), (1, 17), (33, 8)])
def test_the_absorbed_form_is_the_literal_one(lens):
    """One token of a sequence: W_kvb^K folded into the query and W_kvb^V
    applied to `sum p c` (what the decode path runs, here its twin over a
    pool) against every row up-projected first."""
    q_nope, q_rope, rows, w = _operands(jax.random.PRNGKey(2), 2, 1, 40)
    cl = jnp.asarray(lens, jnp.int32)
    visible = jnp.arange(40)[None, None, :] < cl[:, None, None]
    pool = rows.reshape(10, 8, -1)
    bt = jnp.arange(10, dtype=jnp.int32).reshape(2, 5)
    with HI:
        lit = la.attend_rows(q_nope, q_rope, rows, w, visible, 0.2)
        q = la.cache_rows(
            jnp.einsum("btnd,lnd->btnl", q_nope, w[..., :DN]), q_rope,
            la.row_lanes(LC, DR))[:, 0]
        o_lat = la.latent_decode_xla(q, pool, bt, cl, scale=0.2, lc=LC)
        ab = jnp.einsum("bnl,lnv->bnv", o_lat, w[..., DN:])
    assert lit.shape == (2, 1, NH, DV) and _rel(ab, lit[:, 0]) < 1e-5


def test_adjacent_pairing_scores_as_the_published_transpose():
    """The published code views the rotary lanes as pairs, transposes them
    to halves and turns lane i with i + d / 2; the program turns (2i, 2i +
    1) in place. The rotated vectors differ by one permutation of the
    lanes, common to q_rope and k_r, so every score is the same; and
    pairing the halves WITHOUT the transpose is another function."""
    k = jax.random.split(jax.random.PRNGKey(3), 2)
    q = jax.random.normal(k[0], (1, 30, NH, DR))
    kr = jax.random.normal(k[1], (1, 30, 1, DR))
    f = rope.rope_angles(0, 30, DR, 32e6)
    qa, ka = (rope.apply_rotary_emb(x, f) for x in (q, kr))
    qp, kp = (ref.rope_published(x, 32e6) for x in (q, kr))
    perm = np.concatenate([np.arange(0, DR, 2), np.arange(1, DR, 2)])
    np.testing.assert_allclose(qa[..., perm], qp, atol=1e-5)
    score = lambda a, b: jnp.einsum("btnr,bsr->bnts", a, b[:, :, 0])  # noqa: E731
    np.testing.assert_allclose(score(qa, ka), score(qp, kp), atol=1e-4)
    qn, kn = (ref.rope_published(x, 32e6, transpose=False) for x in (q, kr))
    assert _rel(score(qn, kn), score(qp, kp)) > 0.1


# (3) the kernels, interpreted, against their twins --------------------------

def _pool(key, n_blocks=40, bs=8, dtype=jnp.float32):
    k = jax.random.split(key, 2)
    return la.cache_rows(jax.random.normal(k[0], (n_blocks, bs, LC), dtype),
                         jax.random.normal(k[1], (n_blocks, bs, DR), dtype),
                         la.row_lanes(LC, DR))


def _tables(B, per_slot, width):
    bt = np.zeros((B, width), np.int32)
    for b in range(B):
        bt[b, :per_slot] = 1 + b * per_slot + np.arange(per_slot)
    return jnp.asarray(bt)


# (table width, live blocks a slot, lengths). 8-row tiles under a table 10
# wide walk as (8, 16), under 20 as (8, 16) too (`_latent_walk`: eight tiles
# = 64 rows an update, sixteen in the ring), under 3 as (2, 4)
_DECODE_LENS = {
    # a partial last tile, a full table, odd lengths
    "partial": (10, 9, (5, 64, 17, 33)),
    # one row; one tile exactly; one tile past an update
    "edges": (10, 9, (1, 8, 9, 72)),
    # dead slots: their one fetch is the null block
    "dead": (10, 9, (0, 40, 0, 3)),
    # exactly one update, one tile more, one tile short of two updates, two
    "update_edges": (20, 19, (64, 72, 120, 128)),
    # every remainder's halves: 7 = 4 + 2 + 1 tiles, 5 = 4 + 1, 6 = 4 + 2, 3
    "remainders": (20, 19, (56, 40, 48, 24)),
    # shorter than the ring behind one longer than it, and the other way
    "ring": (20, 19, (152, 17, 150, 127)),
    # dead slots between live ones, each live one across an update's edge
    "dead_between": (20, 19, (0, 152, 0, 65)),
    # a table narrower than an update: (2, 4)
    "narrow": (3, 3, (24, 1, 9, 17)),
}


def _decode_operands(case):
    """(q, pool, block tables, lengths) of four slots, eight heads."""
    width, per_slot, lens = _DECODE_LENS[case]
    pool = _pool(jax.random.PRNGKey(4), n_blocks=4 * per_slot + 4)
    k = jax.random.split(jax.random.PRNGKey(5), 2)
    q = la.cache_rows(jax.random.normal(k[0], (4, 8, LC)),
                      jax.random.normal(k[1], (4, 8, DR)),
                      la.row_lanes(LC, DR))
    return q, pool, _tables(4, per_slot, width), jnp.asarray(lens, jnp.int32)


@pytest.mark.parametrize("case", list(_DECODE_LENS))
def test_decode_kernel_against_its_twin(case):
    q, pool, bt, cl = _decode_operands(case)
    lens, width = np.asarray(cl), bt.shape[1]
    assert la.latent_flash_decode_decline(q, pool, bt, LC) is None
    got = la.latent_flash_decode(q, pool, bt, cl, scale=0.2, lc=LC,
                                 interpret=True)
    want = la.latent_decode_xla(q, pool, bt, cl, scale=0.2, lc=LC)
    live = lens > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-5)
    # a dead block is the null block of a zeroed table row: the same rows
    # behind ANOTHER table's dead tail read the same
    dead = np.arange(width)[None, :] * 8 >= np.maximum(lens, 1)[:, None]
    bt2 = jnp.where(dead, 2, bt)
    again = la.latent_flash_decode(q, pool, bt2, cl, scale=0.2, lc=LC,
                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(got)[live],
                                  np.asarray(again)[live])


@pytest.mark.parametrize("order", [(3, 2, 1, 0), (1, 3, 0, 2)])
def test_decode_row_is_blind_to_its_neighbours(order):
    """A sequence's output BITS are its own query's, blocks' and length's
    doing: not its row's in the batch nor what shares the ring with it (the
    repeat share of `correct` replays a prompt in another slot, beside
    other neighbours)."""
    q, pool, bt, cl = _decode_operands("ring")
    run = functools.partial(la.latent_flash_decode, scale=0.2, lc=LC,
                            interpret=True)
    o = jnp.asarray(order)
    np.testing.assert_array_equal(
        np.asarray(run(q, pool, bt, cl))[np.asarray(order)],
        np.asarray(run(q[o], pool, bt[o], cl[o])))


@pytest.mark.parametrize("n_max,tile,want", [
    (136, 128 * 640 * 2, (8, 16)),      # the cell's call: 1.3 MB an update
    (136, 256 * 640 * 2, (4, 8)),       # tiles twice as heavy: half as many
    (136, 8 * 2 ** 20, (1, 2)),         # a tile over the update's bytes
    (20, 8 * 256 * 4, (8, 16)),         # the tests' tiles: the count binds
    (5, 8 * 256 * 4, (4, 8)),           # a power of two inside the table
    (3, 8 * 256 * 4, (2, 4)),
    (1, 8 * 256 * 4, (1, 2)),
])
def test_the_walk_follows_the_tile_and_the_table(n_max, tile, want):
    assert la._latent_walk(n_max, tile) == want


def test_the_gates_vmem_sum_is_the_walks(monkeypatch):
    """At the cell's call (64 slots, 32 heads, 128 x 640 bf16 tiles, a table
    136 wide) the walk is what PERF.md section 7 records, (8, 16), and the
    gate's VMEM sum is computed from it: the ring is sixteen tiles of it,
    an update's float32 scores eight tiles'."""
    from distributed_pytorch_tpu.compat import VMEM_LIMIT_BYTES
    bf = jnp.bfloat16
    q = jax.ShapeDtypeStruct((64, 32, 640), bf)
    pool = jax.ShapeDtypeStruct((8200, 128, 640), bf)
    bt = jax.ShapeDtypeStruct((64, 136), jnp.int32)
    rest = 2 * 32 * (640 + 512) * 2 + 32 * (512 + 256) * 4 + 2 * 32 * 512 * 4
    need = la._decode_vmem_bytes(q, pool, bt, 512)
    assert need == 16 * 163840 + rest + 3 * 8 * 32 * 128 * 4
    assert need < VMEM_LIMIT_BYTES // 8
    monkeypatch.setattr(la, "_latent_walk", lambda n_max, tile: (4, 8))
    assert la._decode_vmem_bytes(q, pool, bt, 512) \
        == 8 * 163840 + rest + 3 * 4 * 32 * 128 * 4
    monkeypatch.setattr(la, "_latent_walk", lambda n_max, tile: (64, 512))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert "MiB of VMEM" in la.latent_flash_decode_decline(q, pool, bt, 512)


# (offset, chunk rows, table width, live blocks). 8-row blocks, four to a key
# tile where the table is that wide
_CHUNKS = [
    # a chunk at offset 0, at nonzero offsets (the key tiles before it
    # whole, the diagonal one masked), a chunk that is one key tile's part,
    # and one that fills the table
    (0, 16, 10, 9), (8, 16, 10, 9), (40, 16, 10, 9), (24, 8, 10, 9),
    (0, 64, 10, 9),
    # Ling's shape, a chunk of two blocks under key tiles of four, at every
    # place in a tile: the tile's later blocks wholly past the chunk's last
    # row (offset 32, and 0 above), the chunk ending with its tile (16, 48)
    (16, 16, 10, 9), (32, 16, 10, 9), (48, 16, 10, 9),
    # one key tile a call (a front and its back in ONE grid step), two, three
    (8, 8, 10, 9), (24, 16, 10, 9), (56, 16, 10, 9),
    # a table narrower than `_CHUNK_GROUP` blocks: a key tile of three, of two
    (0, 16, 3, 3), (8, 16, 3, 3), (16, 8, 3, 3), (0, 16, 2, 2), (8, 8, 2, 2),
]


def _chunk_call_operands(off, T, width, live):
    pool = _pool(jax.random.PRNGKey(6))
    bt = _tables(4, live, width)[2:3]
    q_nope, q_rope, _, w = _operands(jax.random.PRNGKey(7), 1, T, 1)
    assert la.latent_flash_prefill_decline(q_nope, q_rope, pool, w,
                                           bt) is None
    return q_nope, q_rope, pool, w, bt


@pytest.mark.parametrize("off,T,width,live", _CHUNKS)
def test_chunk_kernel_against_its_twin(off, T, width, live):
    q_nope, q_rope, pool, w, bt = _chunk_call_operands(off, T, width, live)
    got = la.latent_flash_prefill(q_nope, q_rope, pool, w, bt,
                                  jnp.int32(off), scale=0.2, interpret=True)
    want = la.latent_chunk_xla(q_nope, q_rope, pool, w, bt, off, scale=0.2)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("off,T,width,live", [
    (0, 16, 10, 9), (32, 16, 10, 9), (8, 8, 10, 9), (24, 16, 10, 9),
    (0, 16, 3, 3)])
def test_chunk_kernel_never_reads_past_the_chunk(off, T, width, live):
    """Every pool block past the chunk's last row holds NaN: the output is
    finite and what it was. A key tile's blocks past the last needed one
    are views of that one again, a key tile past it is not computed."""
    q_nope, q_rope, pool, w, bt = _chunk_call_operands(off, T, width, live)
    run = functools.partial(la.latent_flash_prefill, q_nope, q_rope,
                            w_kvb=w, block_tables=bt, off=jnp.int32(off),
                            scale=0.2, interpret=True)
    want = run(pool=pool)
    used = np.asarray(bt)[0, :-(-(off + T) // pool.shape[1])]
    past = np.setdiff1d(np.arange(pool.shape[0]), used)
    got = run(pool=pool.at[past].set(jnp.nan))
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("off", [0, 8, 24, 40])
def test_chunk_kernel_with_several_query_tiles(off, monkeypatch):
    """A score tile cut to 16 rows: a chunk of 32 is two query tiles over
    ONE key axis, whose end is the last tile's. The first one's last live
    key tile comes earlier: it ends with its own back and writes its rows
    there, the steps after it do nothing."""
    monkeypatch.setattr(la, "_CHUNK_SCORE_BYTES", 16 * 32 * 4)
    assert la._chunk_tiles(32, 10, 8) == (16, 4)
    q_nope, q_rope, pool, w, bt = _chunk_call_operands(off, 32, 10, 9)
    got = la.latent_flash_prefill(q_nope, q_rope, pool, w, bt,
                                  jnp.int32(off), scale=0.2, interpret=True)
    want = la.latent_chunk_xla(q_nope, q_rope, pool, w, bt, off, scale=0.2)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_chunk_tiles_at_the_cells_shape():
    # the whole 1,024-row chunk is one query tile against 4 blocks: a key
    # tile is up-projected once a head
    assert la._chunk_tiles(1024, 136, 128) == (1024, 4)
    assert la._chunk_tiles(16, 10, 8) == (16, 4)
    assert la._chunk_tiles(64, 2, 8) == (64, 2)


# (4) the gates and what they say --------------------------------------------

def _decode_call(**kw):
    a = dict(q=jnp.zeros((4, 8, 128)), pool=jnp.zeros((9, 8, 128)),
             bt=jnp.zeros((4, 3), jnp.int32), lc=LC)
    a.update(kw)
    return la.latent_flash_decode_decline(a["q"], a["pool"], a["bt"],
                                          a["lc"])


@pytest.mark.parametrize("change,told", [
    ({}, None),
    ({"q": jnp.zeros((4, 1, 8, 128))}, "is not (B, nh, lanes)"),
    ({"q": jnp.zeros((4, 8, 128), jnp.int32),
      "pool": jnp.zeros((9, 8, 128), jnp.int32)}, "query dtype"),
    ({"pool": jnp.zeros((9, 8, 128), jnp.bfloat16)}, "of the queries'"),
    ({"pool": jnp.zeros((9, 8, 2, 64))}, "is not (n_blocks, bs, lanes)"),
    ({"pool": jnp.zeros((9, 12, 128))}, "is not a multiple of 8"),
    ({"pool": jnp.zeros((9, 8, 100)), "q": jnp.zeros((4, 8, 100))},
     "no whole tiles of 8"),
    ({"q": jnp.zeros((4, 8, 64))}, "against rows of 128"),
    ({"lc": 20}, "20 of them the latent"),
    ({"q": jnp.zeros((4, 6, 128))}, "6 query rows"),
    ({"pool": jnp.zeros((9, 1 << 14, 1 << 10)),
      "q": jnp.zeros((4, 8, 1 << 10))}, "MiB of VMEM"),
])
def test_decode_gate(change, told):
    why = _decode_call(**change)
    assert (why is None) if told is None else (told in why), why


def _chunk_call(**kw):
    a = dict(q_nope=jnp.zeros((1, 16, NH, DN)),
             q_rope=jnp.zeros((1, 16, NH, DR)),
             pool=jnp.zeros((9, 8, 128)), w=jnp.zeros((LC, NH, DN + DV)),
             bt=jnp.zeros((1, 3), jnp.int32))
    a.update(kw)
    return la.latent_flash_prefill_decline(a["q_nope"], a["q_rope"],
                                           a["pool"], a["w"], a["bt"])


@pytest.mark.parametrize("change,told", [
    ({}, None),
    ({"q_nope": jnp.zeros((2, 16, NH, DN))}, "not one sequence's"),
    ({"q_nope": jnp.zeros((1, 1, NH, DN))}, "not one sequence's"),
    ({"q_nope": jnp.zeros((1, 12, NH, DN))}, "sublane (8) multiple"),
    ({"q_nope": jnp.zeros((1, 16, NH, DN), jnp.bfloat16)},
     "of the queries'"),
    ({"pool": jnp.zeros((9, 8, 100))}, "no whole tiles of 8"),
    ({"w": jnp.zeros((LC, NH, DN + 12))}, "not whole tiles of 8"),
    ({"w": jnp.zeros((124, NH, DN + DV))}, "not whole tiles of 8"),
    ({"pool": jnp.zeros((9, 1 << 13, 128)),
      "q_nope": jnp.zeros((1, 1 << 13, NH, DN))}, "MiB of VMEM"),
])
def test_chunk_gate(change, told):
    why = _chunk_call(**change)
    assert (why is None) if told is None else (told in why), why


@pytest.mark.parametrize("T,width", [(1024, 136), (256, 42)],
                         ids=["joyai", "ling"])
def test_the_chunk_gates_vmem_sum_is_the_bodys(T, width):
    """At the cell's call (a 1,024-row chunk of 32 heads, 128 x 640 bf16
    blocks, a table 136 wide) and at Ling's (256 rows, 42 wide) the gate
    sums what the two-halves body holds: the blocks the pipeline double
    buffers, the scratch a front leaves for the next step's back (a float32
    score tile, the values, the rows' maxima beside the softmax state) and
    a step's own temporaries."""
    from distributed_pytorch_tpu.compat import VMEM_LIMIT_BYTES
    bf = jnp.bfloat16
    q = jax.ShapeDtypeStruct((1, T, 32, 128), bf)
    pool = jax.ShapeDtypeStruct((8200, 128, 640), bf)
    w = jax.ShapeDtypeStruct((512, 32, 256), bf)
    bt = jax.ShapeDtypeStruct((1, width), jnp.int32)
    assert la._chunk_tiles(T, width, 128) == (T, 4)
    blocks = 2 * (T * 256 + 512 * 256 + T * 128 + 4 * 128 * 640) * 2
    scratch = (T * 128 + 3 * T * 128 + T * 512) * 4 + 512 * 128 * 2
    # the stacked tile, [k_nope | v] in float32 and bf16, the scores' key
    # operand; a score tile, p and p's cast in flight
    temps = (512 * 640 * 2 + 512 * 256 * (4 + 2) + 512 * 256 * 2
             + 3 * T * 512 * 4)
    need = la._prefill_vmem_bytes(q, pool, w, bt)
    assert need == blocks + scratch + temps
    assert need < VMEM_LIMIT_BYTES // 2


def test_a_mesh_declines_both_kernels(monkeypatch):
    from distributed_pytorch_tpu.parallel import context

    class TwoChips:
        devices = np.zeros((2, 1))
    monkeypatch.setattr(context, "get_mesh", lambda: TwoChips)
    assert "multi-device mesh" in _decode_call()
    assert "multi-device mesh" in _chunk_call()


@pytest.mark.parametrize("mode,told", [
    ("auto", "gather+naive (FLASH_DECODE=auto)"),
    ("on", "latent_flash_prefill (FLASH_DECODE=on) | latent_flash_decode "
           "(FLASH_DECODE=on)"),
    ("off", "gather+naive (FLASH_DECODE=off)"),
])
def test_the_paths_line_says_which_ran(mv, monkeypatch, mode, told):
    """No option picks a path: the gates choose from shapes and device,
    and the existing FLASH_DECODE knob means what it means for every
    paged kernel (off the chip, `on` = interpret mode). Whichever ran, the
    numbers are the twins'."""
    cfg, model, variables = mv
    monkeypatch.setenv("FLASH_DECODE", mode)
    jax.clear_caches()              # the knob is read when a program traces
    caches = init_paged_cache(cfg, 1 + 8, 8, dtype=jnp.float32)
    bt = jnp.asarray(np.concatenate([1 + np.arange(8), [0, 0]])[None],
                     jnp.int32)
    seq = np.asarray(_prompts((24,), seed=9)[0])
    paths.reset()
    with HI:
        got, caches = _teacher_forced(model, variables, cfg, seq, 16, 16, 0,
                                      caches, bt)
        want = ref.forward_logits(variables["params"], LLM_KW,
                                  jnp.asarray(seq[None]))[0]
    assert paths.choices()["decode_attention"] == told
    assert _rel(got, want) < 3e-5


# (5) through the cache and the engine ---------------------------------------

@functools.partial(jax.jit, static_argnums=0)
def _chunk_logits(model, variables, caches, buf, off, bt_row, slot, n):
    logits, _, caches = model.apply(
        variables, buf, None, caches, off, all_logits=True,
        block_tables=bt_row, state_ctx={"slot": slot, "valid_len": n})
    return logits, _strip(model.config, caches)


@functools.partial(jax.jit, static_argnums=0)
def _token_logits(model, variables, caches, tok, pos, bt, live):
    logits, _, caches = model.apply(
        variables, tok[:, None], None, caches, pos, block_tables=bt,
        state_ctx={"live": live})
    return logits, _strip(model.config, caches)


def _teacher_forced(model, variables, cfg, seq, lens, chunk, slot, caches,
                    bt):
    """Prefill `lens` ids in chunks of `chunk` rows into `slot`, then one
    token at a time beside dead slots: every position's logits."""
    rows = []
    for off in range(0, lens, chunk):
        n = min(chunk, lens - off)
        buf = np.zeros((1, chunk), np.int32)
        buf[0, :n] = seq[off:off + n]
        logits, caches = _chunk_logits(
            model, variables, caches, jnp.asarray(buf), jnp.int32(off),
            bt[slot:slot + 1], jnp.int32(slot), jnp.asarray([n], jnp.int32))
        rows.extend(np.asarray(logits[0, :n]))
    for i in range(lens, len(seq)):
        tok = np.zeros(bt.shape[0], np.int32)
        pos = np.zeros(bt.shape[0], np.int32)
        live = np.zeros(bt.shape[0], bool)
        tok[slot], pos[slot], live[slot] = seq[i], i, True
        logits, caches = _token_logits(
            model, variables, caches, jnp.asarray(tok), jnp.asarray(pos),
            bt, jnp.asarray(live))
        rows.append(np.asarray(logits[slot, -1]))
    return np.stack(rows), caches


@pytest.mark.parametrize("impl", ["twins", "kernels"])
def test_cache_path_across_chunks_and_a_used_slot(mv, impl, monkeypatch):
    """Chunks of 16: a prompt of 45 (three chunks, the last part-filled),
    30 tokens decoded behind it through the paged latent cache, then a
    shorter sequence into the SAME slot's blocks, which still hold the
    first one's rows: every position's logits against the reference's full
    forward pass."""
    cfg, model, variables = mv
    if impl == "kernels":
        monkeypatch.setenv("FLASH_DECODE", "on")    # interpret mode here
    jax.clear_caches()              # the knob is read when a program traces
    paths.reset()
    caches = init_paged_cache(cfg, 1 + 16, 8, dtype=jnp.float32)
    bt = np.zeros((2, 16 + 2), np.int32)
    bt[1, :16] = 1 + np.arange(16)
    bt = jnp.asarray(bt)
    for lens, total, seed in ((45, 75, 7), (13, 40, 8)):
        seq = np.asarray(_prompts((total,), seed=seed)[0])
        with HI:
            got, caches = _teacher_forced(model, variables, cfg, seq, lens,
                                          16, 1, caches, bt)
            want = ref.forward_logits(variables["params"], LLM_KW,
                                      jnp.asarray(seq[None]))[0]
        assert _rel(got, want) < 3e-5, (impl, lens)
    assert ("latent_flash_decode" in paths.choices()["decode_attention"]) \
        == (impl == "kernels")


def test_the_engine_emits_the_references_tokens(mv):
    """Greedy tokens through the engine's own programs (chunks beside
    decoding slots, slots reused): every emitted token is the reference's
    argmax on the sequence so far; and the two counters of the latent
    layers, booked at the plan."""
    cfg, model, variables = mv
    eng = DecodeEngine(model, variables, n_slots=3, max_len=128,
                       block_size=8, prefill_chunk=16, temperature=0.0,
                       min_bucket=8, prefix_cache=False)
    assert eng.features_declined == []
    prompts = _prompts((5, 37, 50, 23, 41), seed=11)
    with HI:
        outs = eng.run(prompts, 30)
        for prompt, full in zip(prompts, outs):
            logits = ref.forward_logits(
                variables["params"], LLM_KW,
                jnp.asarray([full[:-1]], jnp.int32), last=30)[0]
            assert np.array_equal(np.asarray(logits).argmax(-1),
                                  np.asarray(full[len(prompt):]))
    # three latent layers; a prompt's rows see what a causal mask lets
    # them, however it was cut in chunks; a chunk call reads the rows
    # before it and its own, a decode call every row of its sequence
    assert eng.chunk_attn_pairs_by["full"] == 3 * sum(
        n * (n + 1) // 2 for n in map(len, prompts))
    chunk_rows = sum(min(off + 16, n) for n in map(len, prompts)
                     for off in range(0, n, 16))
    assert eng.latent_rows_read_by["chunk"] == 3 * chunk_rows
    # the first token comes with the last chunk; 29 more by decode calls
    # that read the prompt and every token so far
    decode_rows = sum(n + i for n in map(len, prompts) for i in range(1, 30))
    assert eng.latent_rows_read_by["decode"] == 3 * decode_rows
    assert eng.latent_rows_read_by == eng.kv_rows_read_full_by
    by = eng.resident_bytes_by_kind
    assert by["pools"] == 3 * eng.n_blocks * 8 * 128 * 4 \
        and by["window"] == by["slot_state"] == 0


def test_counters_reach_metrics_and_the_timeline(mv):
    from distributed_pytorch_tpu.serve.scheduler import Scheduler
    cfg, model, variables = mv
    eng = DecodeEngine(model, variables, n_slots=2, max_len=64, block_size=8,
                       prefill_chunk=16, temperature=0.0, min_bucket=8,
                       prefix_cache=False)
    sched = Scheduler(eng, max_queue=4)
    with HI:
        eng.run(_prompts((20, 9)), 4)
    got = {}
    for line in sched.metrics.render_prometheus().splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.partition(" ")
            got[name] = float(value)
    assert got["serve_latent_rows_read_total"] == eng.latent_rows_read > 0
    assert got["serve_kv_rows_read_full_total"] == eng.latent_rows_read
    assert got["serve_resident_bytes_pools"] == \
        eng.resident_bytes_by_kind["pools"] > 0
    last = eng.flight.entries()[-1]
    assert "kv_rows_read_full" in last


def test_int8_cache_is_declined_for_a_latent_pool(mv):
    from distributed_pytorch_tpu.ops import quant
    cfg, model, variables = mv
    assert not quant.quant_kv_usable(cfg)
    with pytest.raises(AssertionError, match="no int8 form"):
        init_paged_cache(cfg, 9, 8, dtype=jnp.int8)


@pytest.mark.parametrize("change,told", [
    ({"attn": "gqa"}, "says attn 'mla'"),
    ({"layer_pattern": "LF*ELE"}, "no GQA layer beside them"),
    ({"pos_emb": "learn"}, "pos_emb 'rope'"),
    ({"rope_head_dim": 7}, None),
    ({"layer_pattern": "FEFEFE"}, "its 'L' layers'"),
])
def test_an_inconsistent_latent_configuration_is_refused(change, told):
    with pytest.raises(AssertionError, match=told):
        LLMConfig(**{**LLM_KW, **change})


def test_the_classic_models_are_not_asked():
    with pytest.raises(AssertionError, match="an 'L' layer's"):
        LLMConfig(attn="mla", qk_nope_head_dim=16)
    LLMConfig(attn="mla")           # the classic block's MLA, as before
