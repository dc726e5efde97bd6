"""Pallas flash-attention kernel vs the naive einsum oracle.

Runs the kernel in interpret mode (no TPU needed) and checks forward and
backward numerics against `_naive_sdpa` — the reference-semantics path
(reference model.py:149 SDPA / :225-226 causal mask).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu.ops import flash_attention as fa
from distributed_pytorch_tpu.ops.attention_core import _naive_sdpa
from distributed_pytorch_tpu.ops.flash_attention import (
    flash_attention, flash_attention_usable)


def rand_qkv(key, B, T, S, nh, nkv, hs, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, T, nh, hs), dtype)
    k = jax.random.normal(kk, (B, S, nkv, hs), dtype)
    v = jax.random.normal(kv, (B, S, nkv, hs), dtype)
    return q, k, v


def slabs_of(monkeypatch, w, T, S, block_q=0, block_k=0, causal=True):
    """Patch the slab width to `w` rows (0: leave the module's) and return
    the call's slab plan. The kernels take the width as a static argument,
    so a patched constant is seen whatever an earlier test traced."""
    if w:
        monkeypatch.setattr(fa, "SLAB_W", w)
    return fa.slab_plan(T, S, causal, block_q, block_k)[0]


CASES = [
    # (T, S, nh, nkv, hs, block, slab rows (0: SLAB_W as it is), slabs a
    #  diagonal tile (0: the whole masked tile))
    (128, 128, 4, 4, 32, 64, 0, 0),     # MHA, small head dim
    (256, 256, 4, 2, 64, 128, 0, 0),    # GQA group 2
    (128, 128, 4, 1, 64, 64, 0, 0),     # MQA
    (64, 256, 2, 2, 64, 64, 0, 0),      # prefill: S > T (cache tail masked)
    (96, 96, 2, 2, 64, 32, 0, 0),       # non-power-of-two T, odd block split
    # causal row slabs inside a diagonal tile
    (256, 256, 4, 4, 32, 256, 128, 2),  # ONE tile of two slabs, MHA
    (512, 512, 2, 2, 64, 512, 0, 2),    # ONE tile at the module's own width
    (256, 256, 4, 2, 64, 128, 32, 4),   # GQA, a diagonal and a lower tile
    (128, 128, 4, 1, 64, 64, 16, 4),    # MQA, likewise
    (128, 256, 2, 2, 32, 64, 32, 2),    # S > T, equal blocks: tiles past
                                        # the diagonal one are skipped
    (96, 96, 2, 2, 64, 96, 32, 3),      # three slabs
    (64, 64, 2, 2, 32, 32, 32, 0),      # a tile under two slabs: whole
]


@pytest.mark.parametrize("T,S,nh,nkv,hs,block,slab,n_slabs", CASES)
def test_forward_matches_naive(T, S, nh, nkv, hs, block, slab, n_slabs,
                               monkeypatch):
    plan = slabs_of(monkeypatch, slab, T, S, block, block)
    assert (plan[1] if plan else 0) == n_slabs
    q, k, v = rand_qkv(jax.random.PRNGKey(0), 2, T, S, nh, nkv, hs)
    scale = 1.0 / hs ** 0.5
    out = flash_attention(q, k, v, scale=scale, block_q=block, block_k=block,
                          interpret=True)
    ref = _naive_sdpa(q, k, v, scale=scale, q_offset=0, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# id -> (block_q, block_k, slab rows, slabs a diagonal tile)
BWD_TILINGS = {
    "64x32": (64, 32, 16, 0),              # rectangular: no slabs by the rule
    "one_tile_4_slabs": (128, 128, 32, 4),
    "two_tiles_2_slabs": (64, 64, 32, 2),  # a diagonal and a lower tile:
                                           # both bodies in one kernel
}


@pytest.mark.parametrize("tiling", list(BWD_TILINGS))
@pytest.mark.parametrize("nh,nkv,hs", [(4, 4, 32), (4, 2, 64), (8, 1, 16)],
                         ids=["mha_32", "gqa2_64", "mqa_16"])
def test_backward_matches_naive(nh, nkv, hs, tiling, monkeypatch):
    T = 128
    bq, bk, slab, n_slabs = BWD_TILINGS[tiling]
    plan = slabs_of(monkeypatch, slab, T, T, bq, bk)
    assert (plan[1] if plan else 0) == n_slabs
    q, k, v = rand_qkv(jax.random.PRNGKey(1), 2, T, T, nh, nkv, hs)
    scale = 1.0 / hs ** 0.5
    w = jax.random.normal(jax.random.PRNGKey(2), q.shape)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, scale=scale, block_q=bq, block_k=bk,
                              interpret=True)
        return jnp.sum(out * w)

    def loss_naive(q, k, v):
        return jnp.sum(_naive_sdpa(q, k, v, scale=scale, q_offset=0,
                                   causal=True) * w)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gn, "q k v".split()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4, err_msg=f"d{name} mismatch")


def test_bf16_forward_close():
    T, nh, hs = 128, 2, 64
    q, k, v = rand_qkv(jax.random.PRNGKey(3), 1, T, T, nh, nh, hs,
                       dtype=jnp.bfloat16)
    scale = 1.0 / hs ** 0.5
    out = flash_attention(q, k, v, scale=scale, block_q=64, block_k=64,
                          interpret=True)
    ref = _naive_sdpa(q, k, v, scale=scale, q_offset=0, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_usable_gate():
    q, k, v = rand_qkv(jax.random.PRNGKey(0), 1, 128, 128, 2, 2, 64)
    assert flash_attention_usable(q, k, v, causal=True)
    # round 4: the kernel grew a full-attention mode (ring off-diagonal
    # chunks), so non-causal shapes are usable too
    assert flash_attention_usable(q, k, v, causal=False)
    # decode-step shape: single query row -> naive path
    assert not flash_attention_usable(q[:, :1], k, v, causal=True)
    # fp16 not supported on TPU path
    assert not flash_attention_usable(
        q.astype(jnp.float16), k.astype(jnp.float16), v.astype(jnp.float16),
        causal=True)


def test_model_trains_with_pallas_interpret(monkeypatch):
    """End-to-end: the GQA module routed through the pallas impl (interpret
    mode via monkeypatched pallas_call) matches the xla impl."""
    import distributed_pytorch_tpu.ops.flash_attention as fa
    import jax.experimental.pallas as pl

    orig = pl.pallas_call
    monkeypatch.setattr(
        fa.pl, "pallas_call",
        lambda *a, **kw: orig(*a, **{**kw, "interpret": True}))
    # force the dispatcher to believe pallas is available
    import distributed_pytorch_tpu.ops.attention_core as core
    monkeypatch.setattr(core, "_on_tpu", lambda: True)

    from distributed_pytorch_tpu.config import LLMConfig
    from distributed_pytorch_tpu.models.gpt import LLM

    cfg = LLMConfig(vocab_size=128, block_size=64, n_embd=64, n_head=4,
                    n_kv_heads=2, attn="gqa", n_layer=2, up_dim=128,
                    non_linearity="swiglu", pos_emb="rope")
    x = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, 128, jnp.int32)

    def run(impl):
        model = LLM(cfg, attn_impl=impl)
        variables = model.init(jax.random.PRNGKey(5), x, x)

        def loss(params):
            _, l, _ = model.apply({"params": params}, x, x)
            return l
        l, g = jax.value_and_grad(loss)(variables["params"])
        return l, g

    l_p, g_p = run("pallas")
    l_x, g_x = run("xla")
    np.testing.assert_allclose(float(l_p), float(l_x), rtol=1e-5)
    flat_p = jax.tree_util.tree_leaves(g_p)
    flat_x = jax.tree_util.tree_leaves(g_x)
    for a, b in zip(flat_p, flat_x):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4,
                                   atol=5e-4)


def _naive_out_lse(q, k, v, scale, causal):
    nh, nkv = q.shape[2], k.shape[2]
    if nkv != nh:
        k = jnp.repeat(k, nh // nkv, axis=2)
        v = jnp.repeat(v, nh // nkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        T, S = q.shape[1], k.shape[1]
        mask = jnp.arange(T)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask[None, None], s, -1e30)
    lse = jax.nn.logsumexp(s, axis=-1)                    # (B,H,T)
    p = jnp.exp(s - lse[..., None])
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out, jnp.transpose(lse, (0, 2, 1))             # BTNH, (B,T,H)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_lse_matches_naive(causal, monkeypatch):
    """(out, lse) parity for both masking modes — lse is the ring merge's
    contract (ops/ring_attention.py). The causal call's one tile is worked
    in four slabs (lse stays the true logsumexp), the full one in none."""
    from distributed_pytorch_tpu.ops.flash_attention import flash_attention_lse
    plan = slabs_of(monkeypatch, 16, 64, 64, causal=causal)
    assert plan == ((16, 4) if causal else None)
    q, k, v = rand_qkv(jax.random.PRNGKey(3), 2, 64, 64, 4, 2, 16)
    scale = 0.25
    ref_o, ref_l = _naive_out_lse(q, k, v, scale, causal)
    out, lse = flash_attention_lse(q, k, v, scale=scale, causal=causal,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_o),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_l),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("slab", [0, 8], ids=["whole", "slabs_of_8"])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_lse_gradients_including_dlse(causal, slab, monkeypatch):
    """A loss that touches BOTH outputs: the custom vjp must fold d/dlse
    into the delta term correctly (ds = p*(dp - delta + dlse)), in a
    slabbed tile's backward as in a whole one's."""
    from distributed_pytorch_tpu.ops.flash_attention import flash_attention_lse
    plan = slabs_of(monkeypatch, slab, 32, 32, causal=causal)
    assert plan == ((8, 4) if causal and slab else None)
    q, k, v = rand_qkv(jax.random.PRNGKey(4), 1, 32, 32, 2, 2, 16)
    scale = 0.25
    w = jax.random.normal(jax.random.PRNGKey(5), q.shape)
    u = jax.random.normal(jax.random.PRNGKey(6), (1, 32, 2))

    def loss_flash(q, k, v):
        o, l = flash_attention_lse(q, k, v, scale=scale, causal=causal,
                                   interpret=True)
        return jnp.sum(o * w) + jnp.sum(l * u)

    def loss_naive(q, k, v):
        o, l = _naive_out_lse(q, k, v, scale, causal)
        return jnp.sum(o * w) + jnp.sum(l * u)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gn, "q k v".split()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("bq,bk", [(32, 64), (64, 32), (128, 64)])
def test_rectangular_blocks_fwd_bwd(bq, bk, monkeypatch):
    """block_q != block_k exercises the causal-frontier math on
    rectangular tiles (_last_visible_kv/_first_visible_q and the
    DMA-clamp index maps). No tile of a rectangular tiling is slabbed,
    however narrow the slab."""
    T, nh, nkv, hs = 128, 4, 2, 32
    assert slabs_of(monkeypatch, 16, T, T, bq, bk) is None
    q, k, v = rand_qkv(jax.random.PRNGKey(5), 2, T, T, nh, nkv, hs)
    scale = 1.0 / hs ** 0.5
    w = jax.random.normal(jax.random.PRNGKey(6), q.shape)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    flash = loss(lambda q, k, v: flash_attention(
        q, k, v, scale=scale, block_q=bq, block_k=bk, interpret=True))
    naive = loss(lambda q, k, v: _naive_sdpa(
        q, k, v, scale=scale, q_offset=0, causal=True))
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(naive(q, k, v)),
                               rtol=2e-4, atol=2e-4)
    g_f = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    g_n = jax.grad(naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_f, g_n):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("bh,slab", [(2, 0), (4, 0), (8, 0), (2, 16),
                                     (4, 32)])
def test_row_group_blocking_fwd_bwd(bh, slab, monkeypatch):
    """block_h > 1 batches several (batch, head) rows per grid step (the
    grid-overhead fix, PERF.md round 4); MHA only — parity incl. grads.
    A slab of a diagonal tile rides the same leading row axis."""
    B, T, nh, hs = 2, 128, 4, 32
    plan = slabs_of(monkeypatch, slab, T, T, 64, 64)
    assert plan == ((slab, 64 // slab) if slab else None)
    q, k, v = rand_qkv(jax.random.PRNGKey(7), B, T, T, nh, nh, hs)
    scale = 1.0 / hs ** 0.5
    w = jax.random.normal(jax.random.PRNGKey(8), q.shape)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    flash = loss(lambda q, k, v: flash_attention(
        q, k, v, scale=scale, block_q=64, block_k=64, block_h=bh,
        interpret=True))
    naive = loss(lambda q, k, v: _naive_sdpa(
        q, k, v, scale=scale, q_offset=0, causal=True))
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(naive(q, k, v)),
                               rtol=2e-4, atol=2e-4)
    g_f = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    g_n = jax.grad(naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_f, g_n):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_row_group_defaults_to_one_for_gqa():
    """GQA (rep > 1) must not group rows (kv tiles would need strides):
    the default picks g=1 and an explicit block_h > 1 fails loudly."""
    q, k, v = rand_qkv(jax.random.PRNGKey(9), 2, 64, 64, 4, 2, 32)
    out = flash_attention(q, k, v, scale=0.18, block_q=32, block_k=32,
                          interpret=True)  # default g -> 1, works
    ref = _naive_sdpa(q, k, v, scale=0.18, q_offset=0, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    with pytest.raises(AssertionError):
        flash_attention(q, k, v, scale=0.18, block_q=32, block_k=32,
                        block_h=4, interpret=True)


# (T, nh, nkv, hs) -> rows a grid step at B = 16, bf16: one at the full
# 1024 x 1024 tile, as many more as the sequence's one tile is smaller
# (PERF.md section 6, PR 29), one under GQA, fewer where the VMEM limit binds
GROUP_CASES = {
    "T1024": ((1024, 12, 12, 64), 1),
    "T512": ((512, 12, 12, 64), 4),
    "T256": ((256, 12, 12, 64), 16),
    "T256_gqa": ((256, 12, 4, 64), 1),
    "T256_head512_vmem_binds": ((256, 12, 12, 512), 12),
}


@pytest.mark.parametrize("case", list(GROUP_CASES))
def test_default_row_group_scales_with_the_tile(case):
    import warnings

    from distributed_pytorch_tpu.ops import flash_attention as fa
    (T, nh, nkv, hs), want = GROUP_CASES[case]
    bq, bk = fa._pick_block(T, fa.BLOCK_Q), fa._pick_block(T, fa.BLOCK_K)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # the shrink notice
        g = fa._pick_group(16 * nh, nh // nkv, bq, bk, hs, 2)
    assert g == want
    assert fa._vmem_bytes(g, bq, bk, hs, 2) <= fa.VMEM_LIMIT_BYTES
    if case.endswith("vmem_binds"):
        assert fa._vmem_bytes(16, bq, bk, hs, 2) > fa.VMEM_LIMIT_BYTES


class TestDropout:
    """In-kernel attention-weight dropout (round 5; reference
    model.py:149-151 SDPA dropout). The mask is regenerated from the tile
    coordinates in forward and both backward kernels, so the strongest
    check is jax.test_util.check_grads: finite differences validate the
    custom VJP against the (deterministic, seeded) forward itself."""

    def test_rate_zero_identical(self):
        q, k, v = rand_qkv(jax.random.PRNGKey(3), 2, 64, 64, 4, 4, 32)
        base = flash_attention(q, k, v, scale=0.18, block_q=32, block_k=32,
                               interpret=True)
        zero = flash_attention(q, k, v, scale=0.18, block_q=32, block_k=32,
                               dropout_rate=0.0, interpret=True)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(zero))

    def test_dropout_changes_output_and_is_seed_deterministic(self):
        q, k, v = rand_qkv(jax.random.PRNGKey(4), 2, 64, 64, 4, 4, 32)
        rng = jax.random.PRNGKey(7)
        f = functools.partial(flash_attention, scale=0.18, block_q=32,
                              block_k=32, interpret=True, dropout_rate=0.3)
        a = f(q, k, v, dropout_rng=rng)
        b = f(q, k, v, dropout_rng=rng)
        c = f(q, k, v, dropout_rng=jax.random.PRNGKey(8))
        base = flash_attention(q, k, v, scale=0.18, block_q=32, block_k=32,
                               interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert not np.allclose(np.asarray(a), np.asarray(c))
        assert not np.allclose(np.asarray(a), np.asarray(base))

    def test_dropout_mean_preserving(self):
        """Inverted dropout: E[out] == undropped out. Mean over many seeds
        of a single attention row should approach the base output."""
        q, k, v = rand_qkv(jax.random.PRNGKey(5), 1, 32, 32, 2, 2, 32)
        base = flash_attention(q, k, v, scale=0.18, block_q=32, block_k=32,
                               interpret=True)
        outs = [flash_attention(q, k, v, scale=0.18, block_q=32, block_k=32,
                                dropout_rate=0.25,
                                dropout_rng=jax.random.PRNGKey(100 + s),
                                interpret=True)
                for s in range(48)]
        mean = np.mean([np.asarray(o) for o in outs], axis=0)
        # noisy statistic: elementwise tolerance is loose, the bias check
        # is the mean-over-everything one
        np.testing.assert_allclose(mean.mean(), np.asarray(base).mean(),
                                   atol=0.05)
        assert np.abs(mean - np.asarray(base)).mean() < 0.15

    @pytest.mark.parametrize("slab", [0, 8], ids=["whole", "slabs_of_8"])
    @pytest.mark.parametrize("nh,nkv", [(4, 4), (4, 2)])
    def test_dropout_grads_vs_finite_differences(self, nh, nkv, slab,
                                                 monkeypatch):
        from jax.test_util import check_grads
        plan = slabs_of(monkeypatch, slab, 32, 32, 16, 16)
        assert plan == ((8, 2) if slab else None)
        q, k, v = rand_qkv(jax.random.PRNGKey(6), 1, 32, 32, nh, nkv, 32)
        rng = jax.random.PRNGKey(11)

        def f(q, k, v):
            return flash_attention(q, k, v, scale=0.18, block_q=16,
                                   block_k=16, dropout_rate=0.2,
                                   dropout_rng=rng, interpret=True)

        check_grads(f, (q, k, v), order=1, modes=["rev"], atol=2e-2,
                    rtol=2e-2)

    def test_dispatcher_routes_dropout_to_naive_off_tpu(self):
        """Off-TPU the dispatcher must keep the naive dropout path (the
        flash route is TPU-gated)."""
        from distributed_pytorch_tpu.ops.attention_core import sdpa
        q, k, v = rand_qkv(jax.random.PRNGKey(12), 2, 32, 32, 4, 4, 32)
        out = sdpa(q, k, v, dropout_rate=0.5,
                   dropout_rng=jax.random.PRNGKey(0), impl="auto")
        assert np.isfinite(np.asarray(out)).all()

    @pytest.mark.parametrize("nh,nkv", [(2, 2), (4, 2)])
    def test_dropout_exact_vs_replayed_mask_oracle(self, nh, nkv):
        """The hash mask is keyed on absolute positions, so the test can
        replay it on the host and feed an explicit-mask einsum oracle:
        flash-with-dropout must match EXACTLY (not just statistically)."""
        from distributed_pytorch_tpu.ops.flash_attention import _dropout_bits
        B, T, hs, rate = 2, 64, 32, 0.3
        q, k, v = rand_qkv(jax.random.PRNGKey(13), B, T, T, nh, nkv, hs)
        scale = 1.0 / hs ** 0.5
        rng = jax.random.PRNGKey(21)
        out = flash_attention(q, k, v, scale=scale, block_q=32, block_k=16,
                              dropout_rate=rate, dropout_rng=rng,
                              interpret=True)

        seed = jax.random.randint(rng, (2,), -2 ** 31, 2 ** 31 - 1,
                                  jnp.int32)
        bits = _dropout_bits(seed[0], seed[1], 0, 0, 0, (B * nh, T, T))
        thresh = np.uint32(min(int(rate * 2.0 ** 32), 2 ** 32 - 1))
        keep = (np.asarray(bits) >= thresh).astype(np.float32) / (1 - rate)
        keep = keep.reshape(B, nh, T, T)

        kk = np.repeat(np.asarray(k), nh // nkv, axis=2)
        vv = np.repeat(np.asarray(v), nh // nkv, axis=2)
        s = np.einsum("btnh,bsnh->bnts", np.asarray(q, np.float32),
                      kk.astype(np.float32)) * scale
        mask = np.tril(np.ones((T, T), bool))
        s = np.where(mask[None, None], s, -np.inf)
        attn = np.exp(s - s.max(-1, keepdims=True))
        attn /= attn.sum(-1, keepdims=True)
        ref = np.einsum("bnts,bsnh->btnh", attn * keep, vv)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5,
                                   atol=2e-5)


@pytest.mark.parametrize("bq,bk,bh,slab", [
    (32, 32, 1, 0), (16, 64, 4, 0), (64, 16, 8, 0),
    (64, 64, 1, 16),    # the one tile itself, in four slabs
    (32, 32, 2, 16),    # two slabs a diagonal tile, a whole tile below
])
def test_dropout_same_mask_whatever_the_tiling(bq, bk, bh, slab, monkeypatch):
    """The dropout bits are keyed on the absolute (row, query, key)
    position, so the q/kv tile sizes, the row group and a tile's slabs must
    not move the mask: every tiling drops the weights the one-tile call
    drops. (A different mask moves outputs by O(1); the tolerance is the
    online softmax's rounding between tilings.)"""
    q, k, v = rand_qkv(jax.random.PRNGKey(5), 2, 64, 64, 4, 4, 32)
    f = functools.partial(flash_attention, q, k, v, scale=0.18,
                          dropout_rate=0.3, dropout_rng=jax.random.PRNGKey(9),
                          interpret=True)
    assert fa.slab_plan(64, 64, True, 64, 64)[0] is None
    one_tile = f(block_q=64, block_k=64, block_h=1)
    plan = slabs_of(monkeypatch, slab, 64, 64, bq, bk)
    assert plan == ((slab, bq // slab) if slab else None)
    np.testing.assert_allclose(
        np.asarray(f(block_q=bq, block_k=bk, block_h=bh)),
        np.asarray(one_tile), rtol=2e-5, atol=2e-5)


# id -> ((T, S, causal, block_q, block_k), plan, share of the T x S square)
PLAN_CASES = {
    "train_cell": ((1024, 1024, True, 0, 0), (256, 4), 0.625),
    "two_tiles": ((2048, 2048, True, 0, 0), (256, 4), 0.5625),
    "longer_buffer": ((1024, 2048, True, 0, 0), (256, 4), 0.3125),
    "T512_two_slabs": ((512, 512, True, 0, 0), (256, 2), 0.75),
    "T768_three_slabs": ((768, 768, True, 0, 0), (256, 3), 2 / 3),
    "not_causal": ((1024, 1024, False, 0, 0), None, 1.0),
    "rectangular": ((1024, 1024, True, 512, 1024), None, 1.0),
    "rectangular_skips_tiles": ((1024, 1024, True, 256, 512), None, 0.75),
    "under_two_slabs": ((256, 256, True, 0, 0), None, 1.0),
    "tile_not_cut_by_the_slab": ((640, 640, True, 0, 0), None, 1.0),
    "parity_tiles_32": ((64, 64, True, 32, 32), None, 0.75),
}


@pytest.mark.parametrize("case", list(PLAN_CASES))
def test_slab_plan(case):
    """The one static function the kernels, the dispatcher's note and these
    tests read: which calls work their diagonal tiles in slabs, and how
    much of the score square a call computes."""
    args, plan, share = PLAN_CASES[case]
    got_plan, got_share = fa.slab_plan(*args)
    assert got_plan == plan
    assert got_share == pytest.approx(share)


@pytest.mark.parametrize("w,share", [(256, 0.625), (128, 0.5625),
                                     (512, 0.75)])
def test_slab_plan_share_by_width(w, share, monkeypatch):
    monkeypatch.setattr(fa, "SLAB_W", w)
    assert fa.slab_plan(1024, 1024) == ((w, 1024 // w), share)


def test_pallas_dp_mesh_shard_map_wrap(monkeypatch):
    """Under a live multi-device mesh, the dispatcher must run the flash
    kernel per data shard via shard_map (GSPMD can't partition a
    pallas_call) and match the naive oracle."""
    from distributed_pytorch_tpu.ops import attention_core as core
    from distributed_pytorch_tpu.ops import flash_attention as fa
    from distributed_pytorch_tpu.parallel import context
    from distributed_pytorch_tpu.parallel.mesh import MeshPlan, build_mesh

    monkeypatch.setattr(core, "_on_tpu", lambda: True)
    # interpret-mode kernel: patch the public entry the dispatcher calls
    orig = fa.flash_attention
    import functools as ft
    monkeypatch.setattr(
        "distributed_pytorch_tpu.ops.flash_attention.flash_attention",
        ft.partial(orig, interpret=True))
    # assert the shard_map wrap actually engages (gates hold: B % dp == 0)
    calls = []
    orig_wrap = core._shard_map_over_data

    def spy(fn, q, has_rng=False):
        w = orig_wrap(fn, q, has_rng)
        calls.append(w is not None)
        return w

    monkeypatch.setattr(core, "_shard_map_over_data", spy)

    q, k, v = rand_qkv(jax.random.PRNGKey(0), 8, 64, 64, 4, 4, 32)
    mesh = build_mesh(MeshPlan(data=8))
    with context.use_mesh(mesh):
        out = core.sdpa(q, k, v, causal=True, impl="pallas")
    ref = _naive_sdpa(q, k, v, scale=1.0 / 32 ** 0.5, q_offset=0,
                      causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # and the dropout path (per-shard folded rng): finite + correct shape
    with context.use_mesh(mesh):
        outd = core.sdpa(q, k, v, causal=True, impl="pallas",
                         dropout_rate=0.2,
                         dropout_rng=jax.random.PRNGKey(1))
    assert outd.shape == q.shape
    assert np.isfinite(np.asarray(outd)).all()
    assert not np.allclose(np.asarray(outd), np.asarray(out))
    assert calls == [True, True], calls


# ---------------------------------------------------------------------------
# which path `auto` takes on a TPU: decided from the call's shapes, `decode`,
# `q_offset` and the ambient mesh, at trace time (nothing runs on a kernel)
# ---------------------------------------------------------------------------

# id -> (mesh plan | None, q/k/v shape overrides, sdpa kwargs,
#        expected "attention" choice: exact string, or (path, reason part),
#        or None for "no note")
# the kernel's note says how far the causal slabs engaged (`slab_plan`)
FLASH = ("pallas flash (attn_impl=auto; {} causal slabs of 256 rows a "
         "diagonal tile: {} of the score square computed)")
AUTO_CASES = {
    "train_no_mesh": (None, {}, {}, FLASH.format(4, "62.5%")),
    "train_one_device_mesh": (dict(), {}, {}, FLASH.format(4, "62.5%")),
    "train_data_mesh": (dict(data=4), {}, {}, FLASH.format(4, "62.5%")),
    "train_T512": (None, dict(T=512), {},
                   FLASH.format(2, "75.0%")),
    "train_T256": (None, dict(T=256), {},
                   ("xla", "256 keys < 512: XLA's fused attention measured "
                           "faster")),
    "gpt2xl_25_heads": (None, dict(B=2, nh=25), {}, FLASH.format(4, "62.5%")),
    "model_axis_live": (dict(data=2, model=2), {}, {},
                        ("xla", "mesh axis 'model' is live")),
    "pipe_axis_live": (dict(data=2, pipe=2), {}, {},
                       ("xla", "mesh axis 'pipe' is live")),
    "batch_not_over_data": (dict(data=8), dict(B=12), {},
                            ("xla", "batch 12 does not divide over data=8")),
    "decode": (None, {}, dict(decode=True), None),
    "traced_q_offset": (None, {}, dict(q_offset="traced"),
                        ("xla", "q_offset is traced or nonzero")),
    "head_dim_60": (None, dict(hs=60), {},
                    ("xla", "head dim 60 is not a sublane (8) multiple")),
    # beyond the XLA memory guard the unmeasured calls take the kernel too
    "model_axis_live_8192_keys": (dict(data=2, model=2), dict(B=2, T=8192),
                                  {}, FLASH.format(4, "51.6%")),
    "decode_8192_keys": (None, dict(B=2, T=8192), dict(decode=True),
                         FLASH.format(4, "51.6%")),
}


@pytest.mark.parametrize("case", list(AUTO_CASES))
def test_auto_path_on_a_tpu(case, monkeypatch):
    from distributed_pytorch_tpu.obs import paths
    from distributed_pytorch_tpu.ops import attention_core as core
    from distributed_pytorch_tpu.parallel import context
    from distributed_pytorch_tpu.parallel.mesh import MeshPlan, build_mesh

    plan, dims, kwargs, want = AUTO_CASES[case]
    monkeypatch.setattr(core, "_on_tpu", lambda: True)
    d = {"B": 16, "T": 1024, "nh": 12, "hs": 64, **dims}
    x = jax.ShapeDtypeStruct((d["B"], d["T"], d["nh"], d["hs"]),
                             jnp.bfloat16)
    kwargs = dict(kwargs)
    traced = kwargs.pop("q_offset", None) == "traced"

    def call(q, k, v, off):
        if traced:
            kwargs["q_offset"] = off
        return core.sdpa(q, k, v, impl="auto", **kwargs)

    mesh = None if plan is None else build_mesh(MeshPlan(**plan))
    paths.reset()
    with context.use_mesh(mesh):
        out = jax.eval_shape(call, x, x, x,
                             jax.ShapeDtypeStruct((), jnp.int32))
    assert out.shape == x.shape and out.dtype == x.dtype
    got = paths.choices().get("attention")
    if want is None or isinstance(want, str):
        assert got == want
    else:
        path, reason = want
        assert got.startswith(f"{path} (auto: ") and reason in got, got
