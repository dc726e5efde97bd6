"""A Laguna shaped patterned model (window and full attention mixed at two
head counts, per-head output gates, YaRN on half the lanes of the full
layers, a leading dense FFN block, sigmoid-routed gated experts with a shared
one, a head of its own) at a small size on the CPU, seeded weights, float32,
against the plain reference (benchmark/lib/reference_laguna.py): the tree,
the whole forward pass, what each term is worth, the cache path across the
window's edge, a ring's wrap, a chunk boundary and into a used slot, the two
window kernels in interpret mode, the angles against closed forms, the
shares' sum, and what an inconsistent configuration is told."""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_laguna as ref
from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.engine import DecodeEngine
from distributed_pytorch_tpu.models.gpt import LLM, init_paged_cache
from distributed_pytorch_tpu.ops import rope
from distributed_pytorch_tpu.ops import window_attention as wa

# the cell's pattern in little: the leading dense layer behind full
# attention, two sliding layers, a full one, each with its expert layer.
# The window (20) is no multiple of the block (8): the ring has 24 rows
LLM_KW = dict(
    vocab_size=256, block_size=1 << 20, n_embd=64, n_layer=8,
    layer_pattern="*FWEWE*E", pos_emb="rope", rope_theta=5e5,
    rope_pairing="half", rotary_frac=0.5,
    rope_factor=128.0, rope_original_len=64,
    rope_attn_factor=1.4852030263919618, attn_gate=True, window=20,
    window_heads=6, window_rope_theta=1e4, norm_eps=1e-6, tie_head=False,
    attn="gqa", n_head=4, n_kv_heads=2, head_dim=32, attn_bias=False,
    non_linearity="swiglu", up_dim=32, dense_up_dim=160, shared_up_dim=32,
    n_exp=9, n_shared=1, n_act=4, router="sigmoid", routed_scale=2.5)
HI = jax.default_matmul_precision("highest")


def _big(variables):
    """Weights a few times the draw, so that at 64 wide every term moves
    the logits by more than float32 rounding."""
    return jax.tree_util.tree_map(lambda a: a * 6.0 if a.ndim >= 2 else a,
                                  variables)


@pytest.fixture(scope="module")
def mv():
    cfg = LLMConfig(**LLM_KW)
    model = LLM(cfg, compute_dtype=jnp.float32, attn_impl="naive")
    variables = _big(model.init({"params": jax.random.PRNGKey(1)},
                                jnp.zeros((1, 8), jnp.int32)))
    return cfg, model, variables


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).tolist() for n in lens]


def _engine(model, variables, **kw):
    kw = {"n_slots": 2, "max_len": 128, "block_size": 8,
          "prefill_chunk": 16, "temperature": 0.0, "min_bucket": 8,
          "prefix_cache": False, **kw}
    return DecodeEngine(model, variables, **kw)


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
    assert p["lm_head"].shape == p["tkn_emb"]["embedding"].shape  # untied
    full, slide = p["block_0"]["attn"], p["block_2"]["attn"]
    assert set(full) == set(slide) == {"c_attn", "c_gate", "c_proj"}
    assert all(set(m) == {"kernel"} for m in full.values())     # no biases
    assert full["c_attn"]["kernel"].shape == (64, (4 + 2 * 2) * 32)
    assert slide["c_attn"]["kernel"].shape == (64, (6 + 2 * 2) * 32)
    assert full["c_gate"]["kernel"].shape == (64, 4)            # a head
    assert slide["c_gate"]["kernel"].shape == (64, 6)
    assert full["c_proj"]["kernel"].shape == (4 * 32, 64)
    assert slide["c_proj"]["kernel"].shape == (6 * 32, 64)
    assert p["block_1"]["mlp"]["c_fc"].shape == (64, 2 * 160)
    moe = p["block_3"]["moe"]
    assert set(moe) == {"gate", "gate_bias", "experts_up", "experts_down",
                        "shared_up", "shared_down"}
    assert moe["gate"].shape == (64, 8)
    # two kinds of state in the one tree: pools and a ring a slot
    caches = init_paged_cache(cfg, 5, 8, dtype=jnp.float32, n_slots=3)
    assert [None if c is None else sorted(c) for c in caches] == [
        ["k", "v"], None, ["k", "v"], None, ["k", "v"], None, ["k", "v"],
        None]
    assert caches[0]["k"].shape == (5, 8, 128)
    assert caches[2]["k"].shape == (3, 24, 128)         # 20 in blocks of 8
    assert not cfg.recurrent and cfg.slot_state == "window layers"


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


def test_the_shares_add_up_to_the_uncut_layer(mv):
    """Eight chips share a layer's experts: each one's part of the routed
    sum, added, is the layer with every expert held; the shared expert is
    counted once."""
    cfg, model, variables = mv
    whole = variables["params"]["block_3"]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 19, 64))
    kw = dict(k=3, scale=2.5)
    with HI:
        want = ref.experts_forward(h, whole, first=0, **kw)
        parts = 0.0
        for chip in range(4):
            share = dict(whole,
                         experts_up=whole["experts_up"][2 * chip:][:2],
                         experts_down=whole["experts_down"][2 * chip:][:2])
            parts = parts + ref.experts_forward(
                h, share, first=2 * chip, shared=chip == 0, **kw)
            # and the program's share is the reference's
            held = dataclasses.replace(cfg, experts_held=(2 * chip, 2))
            from distributed_pytorch_tpu.models.mlp import RoutedExperts
            got = RoutedExperts(held).apply({"params": share}, h)[0]
            alone = ref.experts_forward(h, share, first=2 * chip, **kw)
            assert _rel(got, alone) < 2e-5
    assert _rel(parts, want) < 1e-5


# (2) through the cache -----------------------------------------------------

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
    token at a time beside a dead slot: every position's logits."""
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


@pytest.mark.parametrize("impl", ["naive", "kernels"])
def test_cache_path_across_window_wrap_chunks_and_a_used_slot(mv, impl,
                                                              monkeypatch):
    """Chunks of 16 into a ring of 24 rows, a prompt of 45 (three chunks,
    the last part-filled, across the window's edge at 20 and the ring's
    wrap at 24), 30 tokens through the ring after it (a second wrap), then
    a shorter sequence into the SAME slot, whose ring still holds the
    first one's rows."""
    cfg, model, variables = mv
    if impl == "kernels":
        monkeypatch.setenv("FLASH_DECODE", "on")    # interpret mode here
        model = LLM(cfg, compute_dtype=jnp.float32, attn_impl="auto")
    caches = init_paged_cache(cfg, 1 + 16, 8, dtype=jnp.float32, n_slots=2)
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


def test_the_engine_emits_the_references_tokens(mv):
    """Greedy tokens through the engine's own programs (chunks beside
    decoding slots, slots reused): every emitted token is the reference's
    argmax on the sequence so far."""
    cfg, model, variables = mv
    eng = _engine(model, variables, n_slots=3)
    prompts = _prompts((5, 37, 50, 23, 41), seed=11)
    with HI:
        outs = eng.run(prompts, 30)
        for prompt, full in zip(prompts, outs):
            logits = ref.forward_logits(
                variables["params"], LLM_KW,
                jnp.asarray([full[:-1]], jnp.int32), last=30)[0]
            assert np.array_equal(np.asarray(logits).argmax(-1),
                                  np.asarray(full[len(prompt):]))
    # what the window layers read, and what they were spared
    assert eng.kv_rows_read_window_by["decode"] > 0
    assert eng.kv_rows_read_window < eng.kv_rows_read_full
    assert eng.window_rows_saved > 0
    # the chunk calls' (query, key) pairs: a prompt's rows see what a
    # causal (and a window) mask lets them, however it was cut in chunks
    assert eng.chunk_attn_pairs_by == {
        "full": 2 * sum(n * (n + 1) // 2 for n in map(len, prompts)),
        "window": 2 * sum(min(i + 1, 20) for p in prompts
                          for i in range(len(p)))}
    last = eng.flight.entries()[-1]
    assert "kv_rows_read_window" in last and "kv_rows_read_full" in last


def test_a_chunk_with_no_tile_split_takes_the_masked_path(mv, monkeypatch):
    """A window model whose chunk the kernel's tiles cannot cut (12 rows
    in blocks of 4: no multiple of the 8-row step) still builds its engine
    under FLASH_DECODE=on: the gate says why, the masked path carries the
    call, and the tokens are those of the gather path."""
    from distributed_pytorch_tpu.obs import paths
    cfg, model, variables = mv
    kw = dict(block_size=4, prefill_chunk=12, min_bucket=4, max_len=64)
    assert wa._chunk_tiles(12, wa.ring_rows(20, 4), 20, 3, True)[:2] == (0, 0)
    prompts = _prompts((30, 17), seed=5)
    monkeypatch.setenv("FLASH_DECODE", "off")
    want = _engine(model, variables, **kw).run(prompts, 4)
    monkeypatch.setenv("FLASH_DECODE", "on")
    eng = _engine(model, variables, **kw)
    assert eng.run(prompts, 4) == want
    q = jnp.zeros((1, 12, 6, 32))
    assert "no tile split" in wa.window_flash_prefill_decline(
        q, jnp.zeros((20 + 12, 128)), 2, 20)
    assert "gather+naive" in str(paths.choices()["decode_attention"])


def test_prefix_reuse_stands_down_for_the_window_state(mv):
    cfg, model, variables = mv
    eng = _engine(model, variables, prefix_cache=True)
    assert eng.features_declined == ["prefix_cache"]
    from distributed_pytorch_tpu.obs import paths
    assert "window layers keep per-slot state" in paths.choices()[
        "prefix_cache"]


def test_a_window_layers_bytes_do_not_know_max_len(mv):
    cfg, model, variables = mv
    short = _engine(model, variables, max_len=64)
    long = _engine(model, variables, max_len=512)
    a, b = short.resident_bytes_by_kind, long.resident_bytes_by_kind
    assert a["window"] == b["window"] == 2 * 2 * 2 * 24 * 128 * 4
    assert b["pools"] > 4 * a["pools"] and a["weights"] == b["weights"]


# (3) the kernels, interpret mode, against the masked XLA path --------------

@pytest.mark.parametrize("window,ring", [(20, 24), (16, 16), (24, 32)])
def test_window_decode_kernel(window, ring):
    """Positions short of the window, at its edge, past a wrap, and a dead
    slot; a window that is no multiple of the tile."""
    B, nh, nkv, hs = 6, 6, 2, 64
    key = jax.random.split(jax.random.PRNGKey(window), 3)
    q = jax.random.normal(key[0], (B, 1, nh, hs))
    rk = jax.random.normal(key[1], (B, ring, 128))
    rv = jax.random.normal(key[2], (B, ring, 128))
    pos = jnp.asarray([0, 5, window - 1, window, 3 * ring + 7, 11])
    live = jnp.asarray([True, True, True, True, True, False])
    kw = dict(window=window, scale=0.125, n_kv_heads=nkv)
    got = wa.window_flash_decode(q[:, 0], rk, rv,
                                 jnp.where(live, pos, -1),
                                 interpret=True, **kw)
    want = wa.window_decode(q, rk, rv, pos, live, **kw)[:, 0]   # XLA here
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert not np.asarray(got[5]).any()
    # and the XLA path against positions written out: slot 4's ring holds
    # positions p - ((p - r) mod ring)
    p = 3 * ring + 7
    held = p - (p - np.arange(ring)) % ring
    seen = (p - held) < window
    k4 = np.asarray(rk[4, :, :nkv * hs]).reshape(ring, nkv, hs)
    v4 = np.asarray(rv[4, :, :nkv * hs]).reshape(ring, nkv, hs)
    for head in range(nh):
        s = (k4[:, head // 3] @ np.asarray(q[4, 0, head])) * 0.125
        w = np.where(seen, np.exp(s - s[seen].max()), 0.0)
        np.testing.assert_allclose(
            want[4, head], (w / w.sum()) @ v4[:, head // 3], atol=2e-5,
            rtol=2e-5)


# (window, ring, off, T, query tile or None, float32 score budget or None):
# a 16-row chunk is one query tile whose five key views share ONE softmax
# update (`_chunk_tiles` at 8-row views); then `off` < ring (the views left
# of position 0 are masked, nothing was zeroed), a chunk whose slot's ring
# had wrapped (`ring_logical` from row off % ring), and, with the tile and
# the budget patched down (no caller sets them), four query tiles of 8 rows
# whose windows take several steps: of each tile's four views two lie whole
# inside the band of all its rows, one holds the causal edge, one the far
_WINDOW_CHUNKS = [(20, 24, 0, 16, None, None), (20, 24, 16, 16, None, None),
                  (20, 24, 48, 16, None, None), (16, 16, 32, 16, None, None),
                  (40, 40, 64, 16, None, None), (20, 24, 8, 16, None, None),
                  (20, 24, 40, 16, None, None), (24, 24, 64, 32, 8, 1),
                  (24, 24, 8, 32, 8, 3 * 8 * 3 * 8 * 4),
                  (20, 24, 72, 32, 8, None)]


@pytest.mark.parametrize("window,ring,off,T,tile_q,score", _WINDOW_CHUNKS)
def test_window_chunk_kernel(window, ring, off, T, tile_q, score,
                             monkeypatch):
    nh, nkv, hs = 6, 2, 64
    key = jax.random.split(jax.random.PRNGKey(off + window), 4)
    q = jax.random.normal(key[0], (1, T, nh, hs))
    # the slot's ring as the engine holds it, position p at row p mod ring
    # (rows of positions under 0: whatever the last occupant left)
    held = jax.random.normal(key[3], (2, ring, 128))
    keys, values = (jnp.concatenate([wa.ring_logical(held[i], off), rows])
                    for i, rows in enumerate(
                        jax.random.normal(key[1 + j], (T, 128))
                        for j in range(2)))
    call = wa.window_flash_prefill
    if tile_q:
        monkeypatch.setattr(wa, "_CHUNK_TILE_Q", tile_q)
        if score:
            monkeypatch.setattr(wa, "_CHUNK_SCORE_BYTES", score)
        call = jax.jit(call.__wrapped__, static_argnames=(
            "window", "scale", "n_kv_heads", "interpret"))
        tq, tk, group, steps = wa._chunk_tiles(T, ring, window, 3, True)
        assert (tq, tk) == (8, 8) and T // tq == 4
        assert (group, steps) == {1: (1, 4), None: (4, 1)}.get(
            score, (3, 2))
    kw = dict(window=window, scale=0.125, n_kv_heads=nkv)
    got = call(q, keys, values, jnp.int32(off), interpret=True, **kw)
    want = wa.window_chunk(q, keys, values, jnp.int32(off), **kw)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    # and the XLA path against positions written out, one head
    pos = off - ring + np.arange(ring + T)
    for t in (0, T - 1):
        seen = (pos >= 0) & (pos <= off + t) & (pos > off + t - window)
        k0 = np.asarray(keys[:, :hs])
        s_ = (k0 @ np.asarray(q[0, t, 0])) * 0.125
        w = np.where(seen, np.exp(s_ - s_[seen].max()), 0.0)
        np.testing.assert_allclose(
            want[0, t, 0], (w / w.sum()) @ np.asarray(values[:, :hs]),
            atol=2e-5, rtol=2e-5)


def test_the_ring_keeps_the_last_real_rows():
    """`ring_logical` and `ring_after` against positions written out: a
    part-filled chunk's pads never reach the ring."""
    R, T, L = 24, 16, 128
    ring = jnp.full((R, L), -1.0)
    where = np.full(R, -1)
    pos = 0
    for valid in (16, 16, 5, 16, 1):
        rows = jnp.broadcast_to(
            jnp.arange(pos, pos + T, dtype=jnp.float32)[:, None], (T, L))
        old = wa.ring_logical(ring, pos)
        for j in range(R):          # in position order, where it holds any
            if pos - R + j >= 0:
                assert float(old[j, 0]) == pos - R + j
        ring = wa.ring_after(jnp.concatenate([old, rows]), R, pos, valid)
        for p in range(pos, pos + valid):
            where[p % R] = p
        pos += valid
        assert np.array_equal(np.asarray(ring[:, 0]).astype(int), where)


# (4) the angles ------------------------------------------------------------

def test_yarn_and_partial_rotation_against_closed_forms():
    theta, n = 5e5, 8192
    assert rope.YARN_BETA == (32.0, 1.0)
    low, high, ramp = rope.yarn_ramp(64, theta, n)
    assert (low, high) == (9, 18)                   # the issue's numbers
    assert low == math.floor(64 * math.log(n / (32 * 2 * math.pi))
                             / (2 * math.log(theta)))
    ramp = np.asarray(ramp)
    assert ramp.shape == (32,) and not ramp[:10].any() \
        and (ramp[18:] == 1).all()
    np.testing.assert_allclose(ramp[10:18], np.arange(1, 9) / 9, rtol=1e-6)
    factor, attn = 128.0, 1.4852030263919618
    f = rope.rope_angles(jnp.asarray([3, 1000]), 1, 64, theta,
                         yarn=(factor, n), attn_factor=attn)
    inv = theta ** (-np.arange(32) / 32.0)
    inv = (1 - ramp) * inv + ramp * inv / factor
    for row, p in enumerate((3, 1000)):
        np.testing.assert_allclose(f[row, 0, :, 0], attn * np.cos(p * inv),
                                   rtol=5e-4, atol=5e-4)
        np.testing.assert_allclose(f[row, 0, :, 1], attn * np.sin(p * inv),
                                   rtol=5e-4, atol=5e-4)
    # the ends: a fast frequency as it was, a slow one over the factor
    assert inv[0] == 1.0 and np.isclose(inv[31], theta ** (-31 / 32) / 128)
    # lanes 64-127 untouched, lane i turned with lane i + 32
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 1, 3, 128))
    y = rope.apply_partial_rotary(x, f, half=True)
    assert np.array_equal(np.asarray(y[..., 64:]), np.asarray(x[..., 64:]))
    c = np.asarray(f[0, 0, 5])
    np.testing.assert_allclose(
        y[0, 0, 1, 5], x[0, 0, 1, 5] * c[0] - x[0, 0, 1, 37] * c[1],
        rtol=1e-5)
    np.testing.assert_allclose(
        y[0, 0, 1, 37], x[0, 0, 1, 37] * c[0] + x[0, 0, 1, 5] * c[1],
        rtol=1e-5)
    # the plain rule is what it was
    plain = rope.rope_angles(7, 4, 16, 1e4)
    assert np.array_equal(np.asarray(plain), np.asarray(rope.rope_angles(
        7, 4, 16, 1e4, yarn=(), attn_factor=1.0)))


# (5) what an inconsistent configuration is told ----------------------------

@pytest.mark.parametrize("change,told", [
    (dict(window=0), "needs `window`"),
    (dict(window_heads=0), "needs `window`"),
    (dict(window_heads=5), "divisible by n_kv_heads"),
    (dict(layer_pattern="*F*E*E*E"), "without a 'W' layer"),
    (dict(rotary_frac=0.3), "no even number of lanes"),
    (dict(rope_factor=0.5), "at least 1"),
    (dict(rope_original_len=0), "yarn"),
    (dict(pos_emb="learn"), "rotary positions"),
])
def test_an_inconsistent_window_configuration_is_refused(change, told):
    with pytest.raises(AssertionError, match=told):
        LLMConfig(**{**LLM_KW, **change})


def test_the_classic_models_are_not_asked():
    with pytest.raises(AssertionError, match="patterned model's"):
        LLMConfig(window=8, window_heads=4)
    with pytest.raises(AssertionError, match="patterned model's"):
        LLMConfig(attn_gate=True)
    assert LLMConfig().slot_state == ""
    assert LLMConfig(**{**LLM_KW, "layer_pattern": "*FWEWEME",
                        "ssm_heads": 4, "ssm_head_dim": 16,
                        "ssm_state": 8}).slot_state == \
        "recurrent layers and window layers"
