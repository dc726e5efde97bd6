"""Split-KV flash-decode kernel (ops/flash_decode.py) vs the naive einsum
oracle: parity across GQA ratios and ragged per-sequence cache lengths
(interpret mode on CPU), the usable gate's decline conditions, and the
dispatcher integration (FLASH_DECODE env routing in ops/attention_core)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu.ops.attention_core import _naive_sdpa, sdpa
from distributed_pytorch_tpu.ops.flash_decode import (flash_decode,
                                                      flash_decode_usable)


def _mk(B, S, nh, nkv, hs, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, 1, nh, hs), dtype)
    k = jax.random.normal(ks[1], (B, S, nkv, hs), dtype)
    v = jax.random.normal(ks[2], (B, S, nkv, hs), dtype)
    return q, k, v


@pytest.mark.parametrize("nkv", [8, 4, 2, 1], ids=lambda n: f"nkv{n}")
def test_parity_gqa_ratios(nkv):
    """Kernel output matches the naive path <= 1e-5 for MHA through MQA,
    with every sequence at a different (ragged) cache length."""
    B, S, nh, hs = 4, 64, 8, 16
    q, k, v = _mk(B, S, nh, nkv, hs)
    cl = jnp.array([1, 7, 33, 64], jnp.int32)
    out = flash_decode(q[:, 0], k, v, cl, scale=hs ** -0.5, interpret=True)
    ref = _naive_sdpa(q, k, v, scale=hs ** -0.5, q_offset=cl - 1)[:, 0]
    assert flash_decode_usable(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_parity_block_split():
    """Multiple KV blocks per sequence: the online max/sum merge across
    grid steps must agree with the single-pass softmax."""
    B, S, nh, nkv, hs = 2, 256, 4, 2, 8
    q, k, v = _mk(B, S, nh, nkv, hs, seed=3)
    cl = jnp.array([100, 256], jnp.int32)
    for block_s in (8, 32, 64):
        out = flash_decode(q[:, 0], k, v, cl, scale=hs ** -0.5,
                           block_s=block_s, interpret=True)
        ref = _naive_sdpa(q, k, v, scale=hs ** -0.5, q_offset=cl - 1)[:, 0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


def test_dead_slot_tail_blocks_fully_skipped():
    """A sequence one token into a 64-slot cache owns one 8-row KV block:
    NaN/inf garbage in every LATER block must not leak into the output —
    the numerical witness that tail blocks are fully predicated off
    (within the last partial block, masked lanes are computed-then-zeroed
    like every flash kernel, so the poison starts at the block boundary)."""
    B, S, nh, nkv, hs = 1, 64, 4, 4, 8
    q, k, v = _mk(B, S, nh, nkv, hs)
    k = k.at[:, 8:].set(jnp.nan)
    v = v.at[:, 8:].set(jnp.inf)
    cl = jnp.array([1], jnp.int32)
    out = flash_decode(q[:, 0], k, v, cl, scale=hs ** -0.5, block_s=8,
                       interpret=True)
    assert bool(jnp.isfinite(out).all())
    # one fully-attended slot: softmax weight 1.0 on v[:, 0]
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(v[:, 0]), atol=1e-5)


def test_usable_gate_declines():
    q, k, v = _mk(2, 64, 8, 4, 16)
    assert flash_decode_usable(q, k, v)
    # multi-token query (prefill shape) is not a decode call
    assert not flash_decode_usable(jnp.zeros((2, 4, 8, 16)), k, v)
    # odd head dim: no sublane tiling
    qo, ko, vo = _mk(2, 64, 8, 4, 12)
    assert not flash_decode_usable(qo, ko, vo)
    # unsplittable cache length
    qs, ks_, vs = _mk(2, 9, 8, 4, 16)
    assert not flash_decode_usable(qs, ks_, vs)
    # integer dtypes
    assert not flash_decode_usable(q.astype(jnp.int32), k, v)


def test_usable_gate_declines_under_live_mesh():
    """GSPMD cannot partition a pallas_call: any live multi-device mesh
    must route decode to the naive path."""
    from distributed_pytorch_tpu.parallel import context
    from distributed_pytorch_tpu.parallel.mesh import mesh_for
    q, k, v = _mk(2, 64, 8, 4, 16)
    mesh = mesh_for("dp")
    with context.use_mesh(mesh):
        assert not flash_decode_usable(q, k, v)
    assert flash_decode_usable(q, k, v)  # gate is contextual, not sticky


def test_sdpa_routes_decode_through_kernel(monkeypatch):
    """FLASH_DECODE=on routes single-token cached sdpa calls through the
    kernel (interpret off-TPU) and matches FLASH_DECODE=off bit-for-bit at
    test tolerance; 'off' pins the naive path."""
    B, S, nh, nkv, hs = 3, 64, 8, 2, 16
    q, k, v = _mk(B, S, nh, nkv, hs, seed=11)
    pos = jnp.array([4, 20, 63], jnp.int32)

    monkeypatch.setenv("FLASH_DECODE", "off")
    ref = sdpa(q, k, v, causal=True, q_offset=pos, decode=True)
    monkeypatch.setenv("FLASH_DECODE", "on")
    out = sdpa(q, k, v, causal=True, q_offset=pos, decode=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def _mk_q8(B, S, nh, nkv, hs, seed=0):
    """Random decode shapes with an int8-quantized cache: returns the
    quantized operands AND the dequantized reference K/V (what the kernel
    must reproduce exactly — quantization error is not the kernel's)."""
    from distributed_pytorch_tpu.ops.quant import dequantize_int8, quantize_kv
    q, k, v = _mk(B, S, nh, nkv, hs, seed=seed)
    kq, ks_ = quantize_kv(k)
    vq, vs = quantize_kv(v)
    kd = dequantize_int8(kq, ks_, q.dtype)
    vd = dequantize_int8(vq, vs, q.dtype)
    return q, kq, ks_, vq, vs, kd, vd


@pytest.mark.parametrize("nkv", [8, 4, 2, 1], ids=lambda n: f"nkv{n}")
def test_parity_int8_gqa_ratios(nkv):
    """int8-cache kernel vs the naive path on the DEQUANTIZED cache:
    <= 1e-5 for MHA through MQA at ragged per-sequence lengths — the
    in-kernel dequant (scales folded into score/probability tiles) is
    exact algebra, so the kernel owes the dequantized reference full
    parity."""
    B, S, nh, hs = 4, 64, 8, 16
    q, kq, ks_, vq, vs, kd, vd = _mk_q8(B, S, nh, nkv, hs)
    cl = jnp.array([1, 7, 33, 64], jnp.int32)
    out = flash_decode(q[:, 0], kq, vq, cl, scale=hs ** -0.5,
                       k_scale=ks_, v_scale=vs, interpret=True)
    ref = _naive_sdpa(q, kd, vd, scale=hs ** -0.5, q_offset=cl - 1)[:, 0]
    assert flash_decode_usable(q, kq, vq)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_parity_int8_block_split():
    """Online max/sum merge across multiple int8 KV blocks (each with its
    own scale rows) agrees with the single-pass softmax."""
    B, S, nh, nkv, hs = 2, 256, 4, 2, 8
    q, kq, ks_, vq, vs, kd, vd = _mk_q8(B, S, nh, nkv, hs, seed=3)
    cl = jnp.array([100, 256], jnp.int32)
    for block_s in (8, 32, 64):
        out = flash_decode(q[:, 0], kq, vq, cl, scale=hs ** -0.5,
                           k_scale=ks_, v_scale=vs, block_s=block_s,
                           interpret=True)
        ref = _naive_sdpa(q, kd, vd, scale=hs ** -0.5, q_offset=cl - 1)[:, 0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


def test_sdpa_int8_kernel_vs_dequant_fallback(monkeypatch):
    """The dispatcher's two int8 routes agree: FLASH_DECODE=on runs the
    in-kernel-dequant path, FLASH_DECODE=off dequantizes up front and
    takes the naive path — same cache, same answer."""
    B, S, nh, nkv, hs = 3, 64, 8, 2, 16
    q, kq, ks_, vq, vs, _, _ = _mk_q8(B, S, nh, nkv, hs, seed=11)
    pos = jnp.array([4, 20, 63], jnp.int32)
    monkeypatch.setenv("FLASH_DECODE", "on")
    out = sdpa(q, kq, vq, causal=True, q_offset=pos, decode=True,
               k_scale=ks_, v_scale=vs)
    monkeypatch.setenv("FLASH_DECODE", "off")
    ref = sdpa(q, kq, vq, causal=True, q_offset=pos, decode=True,
               k_scale=ks_, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_int8_unsplittable_cache_falls_back(monkeypatch):
    """quant_usable-style degrade: an int8 cache whose S the kernel cannot
    tile (S=9) declines the kernel even under FLASH_DECODE=on and the
    dequant+naive fallback carries the call — degrade, don't crash."""
    from distributed_pytorch_tpu.ops.attention_core import _naive_sdpa
    from distributed_pytorch_tpu.ops.quant import dequantize_int8, quantize_kv
    B, S, nh, nkv, hs = 2, 9, 4, 2, 16
    q, k, v = _mk(B, S, nh, nkv, hs, seed=7)
    kq, ks_ = quantize_kv(k)
    vq, vs = quantize_kv(v)
    assert not flash_decode_usable(q, kq, vq)
    pos = jnp.array([3, 8], jnp.int32)
    monkeypatch.setenv("FLASH_DECODE", "on")
    out = sdpa(q, kq, vq, causal=True, q_offset=pos, decode=True,
               k_scale=ks_, v_scale=vs)
    ref = _naive_sdpa(q, dequantize_int8(kq, ks_, q.dtype),
                      dequantize_int8(vq, vs, q.dtype),
                      scale=hs ** -0.5, q_offset=pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_int8_dead_slot_tail_blocks_fully_skipped():
    """The int8 variant shares the cache_len block-skip: poisoned code/scale
    rows past the valid block must not leak (NaN scales would propagate
    through any touched lane)."""
    from distributed_pytorch_tpu.ops.quant import quantize_kv
    B, S, nh, nkv, hs = 1, 64, 4, 4, 8
    q, k, v = _mk(B, S, nh, nkv, hs)
    kq, ks_ = quantize_kv(k)
    vq, vs = quantize_kv(v)
    ks_ = ks_.at[:, 8:].set(jnp.nan)
    vs = vs.at[:, 8:].set(jnp.inf)
    cl = jnp.array([1], jnp.int32)
    out = flash_decode(q[:, 0], kq, vq, cl, scale=hs ** -0.5,
                       k_scale=ks_, v_scale=vs, block_s=8, interpret=True)
    assert bool(jnp.isfinite(out).all())


# ----------------------------------------------------------------------
# paged kernel (block-table scalar prefetch over the pool)
# ----------------------------------------------------------------------

def _merge(rows):
    """(n_blocks, bs, n_kv, hs) head-major rows -> the merged-lane float
    pool leaf (n_blocks, bs, L) the engine declares: heads side by side in
    lanes, zero-padded to a multiple of 128."""
    from distributed_pytorch_tpu.ops.block_pool import kv_lanes, merge_heads
    return merge_heads(rows, kv_lanes(*rows.shape[2:]))


def _mk_paged(B, n_max, bs, nh, nkv, hs, seed=0, extra_blocks=4,
              merged=True):
    """Random pool + shuffled non-contiguous block tables: the logical
    view the kernel must reproduce comes from paged_gather (the oracle
    path the engine's naive fallback uses). `merged=False` keeps the
    head-major rows (what the int8 pools quantize from)."""
    import numpy as np_

    from distributed_pytorch_tpu.ops.block_pool import paged_gather
    n_blocks = 1 + B * n_max + extra_blocks      # + null block 0
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, 1, nh, hs))
    kp = jax.random.normal(ks[1], (n_blocks, bs, nkv, hs))
    vp = jax.random.normal(ks[2], (n_blocks, bs, nkv, hs))
    rng = np_.random.default_rng(seed)
    bt = jnp.asarray(rng.permutation(np_.arange(1, 1 + B * n_max))
                     .reshape(B, n_max).astype(np_.int32))
    kl, vl = paged_gather(kp, bt), paged_gather(vp, bt)
    if merged:
        kp, vp = _merge(kp), _merge(vp)
    return q, kp, vp, bt, kl, vl


# (nh, nkv, hs): MHA through MQA at 16-wide heads (128 lanes or fewer),
# then shapes whose n_kv * hs is NOT a multiple of 128, so pad lanes and a
# head starting at lane 64 of a tile are exercised: gpt2-xl's 25 x 64
# (1600 -> 1664 lanes, the last lane group half pad) and 6 kv heads x 64
# under rep 4 (384 lanes, no pad, rep > 1)
_HEAD_SHAPES = [(8, 8, 16), (8, 4, 16), (8, 2, 16), (8, 1, 16),
                (25, 25, 64), (24, 6, 64), (6, 3, 64)]
_ids = lambda t: "nh%d_nkv%d_hs%d" % t  # noqa: E731


# table width -> per-sequence lengths at 8-row blocks. A grid step of the
# kernel is a sequence: it walks ceil(len / 8) live tiles, up to 4 of them a
# joint update (`_walk_shape` at these tiny tiles: ring of 8, groups of
# min(4, width)), so the cases cover 1, 2, 3 and all `width` live blocks,
# a last group that is short, lengths that end ON a block edge and one row
# past it, an odd width, and one-block sequences between full ones
_PAGED_LENS = {
    "ragged": (8, [1, 7, 33, 64]),
    "block_edges": (8, [8, 16, 24, 64]),
    "one_past_an_edge": (8, [9, 17, 25, 57]),
    "odd_width": (5, [40, 1, 33, 17]),
    "width_3": (3, [24, 1, 9, 17]),
    "one_beside_full": (8, [1, 64, 1, 64]),
}


@pytest.mark.parametrize("lens", list(_PAGED_LENS))
@pytest.mark.parametrize("shape", _HEAD_SHAPES, ids=_ids)
def test_paged_parity_gqa_ratios(shape, lens):
    """Paged kernel vs the naive path on the GATHERED logical cache:
    <= 1e-5 for MHA through MQA at ragged per-sequence lengths, through
    shuffled (non-contiguous, non-monotone) block tables."""
    from distributed_pytorch_tpu.ops.flash_decode import (
        paged_flash_decode, paged_flash_decode_usable)
    nh, nkv, hs = shape
    n_max, cl = _PAGED_LENS[lens]
    B, bs = 4, 8
    q, kp, vp, bt, kl, vl = _mk_paged(B, n_max, bs, nh, nkv, hs)
    assert kp.ndim == 3 and kp.shape[2] % 128 == 0
    cl = jnp.array(cl, jnp.int32)
    assert paged_flash_decode_usable(q, kp, vp, bt, nkv)
    out = paged_flash_decode(q[:, 0], kp, vp, bt, cl, scale=hs ** -0.5,
                             n_kv_heads=nkv, interpret=True)
    ref = _naive_sdpa(q, kl, vl, scale=hs ** -0.5, q_offset=cl - 1)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("bs,want", [(64, (1, 2)), (32, (2, 4)),
                                     (8, (3, 8))],
                         ids=["ring2", "ring4", "ring8"])
def test_paged_walk_follows_the_tile(bs, want):
    """How many tile pairs sit in VMEM and how many go through one joint
    update is read from a tile's bytes and the table's width
    (`_walk_shape`): 64 float32 heads x 128 lanes at 64-row blocks leave
    room for a ring of 2 (one tile an update), at 32 rows for 4 (two), and
    a table of 3 blocks caps the update at 3. Same answers as the naive
    path at each."""
    from distributed_pytorch_tpu.ops import flash_decode as fd
    nh = nkv = 64
    hs, B, n_max = 128, 2, 3
    q, kp, vp, bt, kl, vl = _mk_paged(B, n_max, bs, nh, nkv, hs,
                                      extra_blocks=0)
    assert fd._walk_shape(n_max, fd._pair_bytes(kp)) == want
    cl = jnp.array([3 * bs, bs + 1], jnp.int32)
    out = fd.paged_flash_decode(q[:, 0], kp, vp, bt, cl, scale=hs ** -0.5,
                                n_kv_heads=nkv, interpret=True)
    ref = _naive_sdpa(q, kl, vl, scale=hs ** -0.5, q_offset=cl - 1)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


# cell -> (kv heads, head size, table width = (max_len + prefill_chunk) /
# 128): the four accepted cells whose step programs hold
# `paged_flash_decode`, bf16 pools of 128-row blocks
_CELL_WALKS = {
    "gpt2xl_serve_closed24": (25, 64, 10),          # 852 KB a pair
    "lfm2moe_serve_closed128": (8, 64, 10),         # 256 KB
    "laguna_serve_closed64_long": (8, 128, 136),    # 512 KB
    "falcon_h1_serve_closed64": (4, 128, 20),       # 256 KB
}


@pytest.mark.parametrize("cell", list(_CELL_WALKS))
def test_paged_walk_at_the_accepted_cells(cell):
    """`_walk_shape` answers (4, 8) at every accepted cell's pair bytes and
    table width: a change of the rule (the latent kernel's walk has one of
    its own since PR 60, ops/latent_attention.py `_latent_walk`) cannot
    move their kernels unseen."""
    from distributed_pytorch_tpu.ops import block_pool as bp
    from distributed_pytorch_tpu.ops import flash_decode as fd
    nkv, hs, width = _CELL_WALKS[cell]
    k = jax.ShapeDtypeStruct((9, 128, bp.kv_lanes(nkv, hs)), jnp.bfloat16)
    assert fd._pair_bytes(k) in (851968, 524288, 262144)
    assert fd._walk_shape(width, fd._pair_bytes(k)) == (4, 8)


@pytest.mark.parametrize("n_own", [1, 8, 33, 64],
                         ids=lambda n: f"own{n}rows")
@pytest.mark.parametrize("shape", [(25, 25, 64), (8, 2, 16)], ids=_ids)
def test_paged_row_blind_to_neighbours(shape, n_own):
    """A sequence's output BITS depend on its own query, blocks and length
    alone: not on its row in the batch, on how many tiles its neighbours
    hold (whose fetches share the ring with its own), or on a dead slot
    beside it. The granite cell's repeat share replays a prompt in another
    slot beside other neighbours and parts greedy streams at one bit."""
    from distributed_pytorch_tpu.ops.flash_decode import paged_flash_decode
    nh, nkv, hs = shape
    B, n_max, bs = 4, 8, 8
    q, kp, vp, bt, _, _ = _mk_paged(B, n_max, bs, nh, nkv, hs, seed=7)

    def run(rows, lens):
        rows = jnp.array(rows)
        return paged_flash_decode(
            q[rows, 0], kp, vp, bt[rows], jnp.array(lens, jnp.int32),
            scale=hs ** -0.5, n_kv_heads=nkv, interpret=True)

    # sequence 0: first of a batch of long neighbours; third, behind a dead
    # slot (length 0) and a one-tile one, ahead of a slot at length 1; alone
    # in a batch of itself repeated
    a = run([0, 1, 2, 3], [n_own, 64, 40, 57])[0]
    b = run([3, 1, 0, 2], [0, 5, n_own, 1])[2]
    c = run([0, 0, 0, 0], [n_own] * 4)
    for other in (b, c[0], c[3]):
        np.testing.assert_array_equal(
            np.asarray(a).view(np.uint32), np.asarray(other).view(np.uint32))


@pytest.mark.parametrize("shape", _HEAD_SHAPES, ids=_ids)
@pytest.mark.parametrize("write", ["decode", "spec", "prefill"])
def test_paged_update_gather_round_trip(shape, write):
    """`paged_gather(paged_update(pool, rows))` hands back exactly the
    rows written, through each of the three write branches, and the pad
    lanes of a merged-lane pool stay zero."""
    from distributed_pytorch_tpu.ops.block_pool import (
        kv_lanes, paged_gather, paged_update)
    _, nkv, hs = shape
    B, n_max, bs = 3, 4, 8
    T = {"decode": 1, "spec": 3, "prefill": 2 * bs}[write]
    if write == "prefill":
        B = 1
    rng = np.random.default_rng(nkv * hs + T)
    bt = jnp.asarray(rng.permutation(np.arange(1, 1 + B * n_max))
                     .reshape(B, n_max).astype(np.int32))
    pool = jnp.zeros((1 + B * n_max, bs, kv_lanes(nkv, hs)), jnp.float32)
    rows = jnp.asarray(rng.standard_normal((B, T, nkv, hs)), jnp.float32)
    pos = (jnp.int32(bs) if write == "prefill"
           else jnp.asarray([0, 6, 13][:B], jnp.int32))   # 6 + 3 crosses a block
    pool = paged_update(pool, rows, pos, bt)
    view = paged_gather(pool, bt, (nkv, hs))
    assert view.shape == (B, n_max * bs, nkv, hs)
    for b in range(B):
        p0 = int(np.asarray(pos).reshape(-1)[b if write != "prefill" else 0])
        np.testing.assert_array_equal(np.asarray(view[b, p0:p0 + T]),
                                      np.asarray(rows[b]))
    assert float(jnp.abs(pool[..., nkv * hs:]).sum()) == 0.0
    assert float(jnp.abs(view).sum()) == pytest.approx(
        float(jnp.abs(rows).sum()), rel=1e-6)     # nothing written elsewhere


@pytest.mark.parametrize("nkv", [8, 4, 2, 1], ids=lambda n: f"nkv{n}")
def test_paged_parity_int8(nkv):
    """int8-paged parity matrix: the scale-sidecar pools ride the same
    block-table index map and the in-kernel dequant owes the dequantized
    gathered reference full parity (exact algebra)."""
    from distributed_pytorch_tpu.ops.flash_decode import paged_flash_decode
    from distributed_pytorch_tpu.ops.quant import dequantize_int8, quantize_kv
    B, n_max, bs, nh, hs = 4, 8, 8, 8, 16
    q, kp, vp, bt, _, _ = _mk_paged(B, n_max, bs, nh, nkv, hs, seed=3,
                                    merged=False)
    from distributed_pytorch_tpu.ops.block_pool import paged_gather
    kq, ks_ = quantize_kv(kp)
    vq, vs = quantize_kv(vp)
    cl = jnp.array([2, 9, 40, 64], jnp.int32)
    out = paged_flash_decode(q[:, 0], kq, vq, bt, cl, scale=hs ** -0.5,
                             k_scale=ks_, v_scale=vs, interpret=True)
    kd = dequantize_int8(paged_gather(kq, bt), paged_gather(ks_, bt), q.dtype)
    vd = dequantize_int8(paged_gather(vq, bt), paged_gather(vs, bt), q.dtype)
    ref = _naive_sdpa(q, kd, vd, scale=hs ** -0.5, q_offset=cl - 1)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_paged_dead_blocks_fully_skipped():
    """Blocks past a sequence's last valid one must contribute nothing:
    poison every pool block the 1-token sequence does not own — the
    block-table clamp keeps the DMA on the last valid block, so NaN/inf
    elsewhere cannot leak."""
    from distributed_pytorch_tpu.ops.flash_decode import paged_flash_decode
    B, n_max, bs, nh, nkv, hs = 1, 8, 8, 4, 4, 8
    q, kp, vp, bt, _, _ = _mk_paged(B, n_max, bs, nh, nkv, hs)
    own = int(bt[0, 0])
    mask = jnp.arange(kp.shape[0]) != own
    kp = jnp.where(mask[:, None, None], jnp.nan, kp)
    vp = jnp.where(mask[:, None, None], jnp.inf, vp)
    out = paged_flash_decode(q[:, 0], kp, vp, bt,
                             jnp.array([1], jnp.int32), scale=hs ** -0.5,
                             n_kv_heads=nkv, interpret=True)
    assert bool(jnp.isfinite(out).all())
    # one fully-attended row: softmax weight 1.0 on the owned block's row 0
    np.testing.assert_allclose(
        np.asarray(out[0]),
        np.asarray(vp[own, 0, :nkv * hs].reshape(nkv, hs)), atol=1e-5)


def test_paged_usable_gate_declines():
    from distributed_pytorch_tpu.ops.flash_decode import (
        paged_flash_decode_decline, paged_flash_decode_usable)
    q, kp, vp, bt, _, _ = _mk_paged(2, 4, 8, 8, 4, 16)
    assert paged_flash_decode_usable(q, kp, vp, bt, 4)
    # a merged-lane pool does not say how many heads share its lanes
    assert not paged_flash_decode_usable(q, kp, vp, bt)
    # prefill-shaped query
    assert not paged_flash_decode_usable(jnp.zeros((2, 4, 8, 16)), kp, vp,
                                         bt, 4)
    # block size the hardware cannot tile (9 rows)
    q2, kp2, vp2, bt2, _, _ = _mk_paged(2, 4, 9, 8, 4, 16)
    assert not paged_flash_decode_usable(q2, kp2, vp2, bt2, 4)
    # (bs, L) tiles past the scoped-VMEM budget: 64 heads x 128 lanes
    wide = jnp.zeros((8, 512, 64 * 128))
    assert "VMEM" in paged_flash_decode_decline(
        jnp.zeros((2, 1, 64, 128)), wide, wide, bt, 64)
    # live multi-device mesh -> gather + naive carries sharded decode
    from distributed_pytorch_tpu.parallel import context
    from distributed_pytorch_tpu.parallel.mesh import mesh_for
    with context.use_mesh(mesh_for("dp")):
        assert not paged_flash_decode_usable(q, kp, vp, bt, 4)
    assert paged_flash_decode_usable(q, kp, vp, bt, 4)


def test_sdpa_paged_routes_kernel_vs_gather(monkeypatch):
    """The dispatcher's two paged routes agree: FLASH_DECODE=on runs the
    block-table kernel, 'off' gathers the logical view and takes the
    naive path — same pool, same tables, same answer (bf16 and int8)."""
    from distributed_pytorch_tpu.ops.quant import quantize_kv
    B, n_max, bs, nh, nkv, hs = 3, 8, 8, 8, 2, 16
    q, kp, vp, bt, _, _ = _mk_paged(B, n_max, bs, nh, nkv, hs, seed=11)
    pos = jnp.array([4, 20, 63], jnp.int32)
    monkeypatch.setenv("FLASH_DECODE", "on")
    out = sdpa(q, kp, vp, causal=True, q_offset=pos, decode=True,
               block_tables=bt, n_kv_heads=nkv)
    monkeypatch.setenv("FLASH_DECODE", "off")
    ref = sdpa(q, kp, vp, causal=True, q_offset=pos, decode=True,
               block_tables=bt, n_kv_heads=nkv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    _, kp, vp, _, _, _ = _mk_paged(B, n_max, bs, nh, nkv, hs, seed=11,
                                   merged=False)
    kq, ks_ = quantize_kv(kp)
    vq, vs = quantize_kv(vp)
    monkeypatch.setenv("FLASH_DECODE", "on")
    out8 = sdpa(q, kq, vq, causal=True, q_offset=pos, decode=True,
                k_scale=ks_, v_scale=vs, block_tables=bt)
    monkeypatch.setenv("FLASH_DECODE", "off")
    ref8 = sdpa(q, kq, vq, causal=True, q_offset=pos, decode=True,
                k_scale=ks_, v_scale=vs, block_tables=bt)
    np.testing.assert_allclose(np.asarray(out8), np.asarray(ref8),
                               atol=1e-5, rtol=1e-5)


def test_sdpa_decode_scalar_offset_under_jit(monkeypatch):
    """The legacy generate loop's traced SCALAR position broadcasts to the
    per-sequence cache_len vector inside the dispatcher."""
    B, S, nh, nkv, hs = 2, 32, 4, 4, 8
    q, k, v = _mk(B, S, nh, nkv, hs, seed=5)

    def run(p):
        return sdpa(q, k, v, causal=True, q_offset=p, decode=True)

    monkeypatch.setenv("FLASH_DECODE", "on")
    out = jax.jit(run)(jnp.int32(7))
    monkeypatch.setenv("FLASH_DECODE", "off")
    ref = jax.jit(run)(jnp.int32(7))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


# ----------------------------------------------------------------------
# chunk-prefill kernel (mixed prefill+decode path, round 12)
# ----------------------------------------------------------------------

def _mk_chunk(T, n_max, bs, nh, nkv, hs, seed=0, merged=True):
    """One sequence's pool + shuffled block table for the chunk kernel:
    (1, T, nh, hs) query rows at global positions [off, off+T)."""
    import numpy as np_

    from distributed_pytorch_tpu.ops.block_pool import paged_gather
    n_blocks = 1 + n_max + 4                     # + null block 0
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, T, nh, hs))
    kp = jax.random.normal(ks[1], (n_blocks, bs, nkv, hs))
    vp = jax.random.normal(ks[2], (n_blocks, bs, nkv, hs))
    rng = np_.random.default_rng(seed)
    bt = jnp.asarray(rng.permutation(np_.arange(1, 1 + n_max))
                     .reshape(1, n_max).astype(np_.int32))
    kl, vl = paged_gather(kp, bt), paged_gather(vp, bt)
    if merged:
        kp, vp = _merge(kp), _merge(vp)
    return q, kp, vp, bt, kl, vl


# (shape, off, T, table width, float32 score-tile budget or None, real rows
# or None): the six head shapes at a fresh sequence, one prior block and
# three, a 16-row chunk over a table of 8 (ONE key tile of 8 blocks = 64
# keys, one query tile: `_chunk_shape` at 8-row blocks); then walks of
# several key tiles and, with the budget cut to a few rows, several query
# tiles (`_CHUNK_SCORE_BYTES` patched: no caller sets a tile)
_CHUNK_SHAPES = [(8, 8, 16), (8, 4, 16), (8, 1, 16), (25, 25, 64),
                 (24, 6, 64), (4, 2, 128)]
_CHUNK_WALKS = {
    # key tile 0 (keys 0-63) lies whole under the chunk at 72-87, tile 1
    # holds the diagonal and 3 live blocks of 8, tile 2 is dead: 11 live
    # blocks are no multiple of the group, the table is wider than they
    "whole_and_edge_rep4": ((24, 6, 64), 72, 16, 24, None, None),
    "whole_and_edge_25x64": ((25, 25, 64), 72, 16, 24, None, None),
    # the same walk under four query tiles of 8 positions: rep 6 (48 rows
    # a tile), rep 1, and 25 heads of 64 (the last lane group half pads)
    "query_tiles_rep6": ((12, 2, 128), 40, 32, 16, 64 * 64 * 4, None),
    "query_tiles_rep1": ((8, 8, 16), 40, 32, 16, 8 * 64 * 4, None),
    "query_tiles_25x64": ((25, 25, 64), 104, 32, 24, 8 * 64 * 4, None),
    # offset 0 under four query tiles: tile i sees its own i + 1 blocks
    "query_tiles_off0": ((12, 2, 128), 0, 32, 16, 64 * 64 * 4, None),
    # a prompt's last chunk: 21 real rows of 32, the table's columns past
    # them on the null block as the engine leaves them
    "partial_last_chunk": ((24, 6, 64), 64, 32, 16, 32 * 64 * 4, 21),
    # a table of ONE block: the whole chunk against one block a step
    "one_block_table": ((8, 4, 16), 0, 8, 1, None, None),
}
_CHUNK_CASES = [pytest.param(shape, off, 16, 8, None, None,
                             id=f"{_ids(shape)}-off{off}")
                for shape in _CHUNK_SHAPES for off in (0, 8, 24)] + \
    [pytest.param(*case, id=name) for name, case in _CHUNK_WALKS.items()]


@pytest.mark.parametrize("shape,off,T,n_max,score,real", _CHUNK_CASES)
def test_chunk_prefill_parity_offsets(shape, off, T, n_max, score, real,
                                      monkeypatch):
    """paged_flash_prefill vs the naive path on the gathered logical
    view: a chunk at block-aligned offsets (fresh sequence, prior blocks)
    attends its prior context plus its own in-chunk causal prefix — MHA
    through MQA, shuffled tables; 25 x 64 ends in a half-pad lane group,
    6 x 64 packs rep 4 into the rows, 2 x 128 is one head a group. Every
    pool block the chunk does not need is poisoned: a view of a
    part-filled key tile, a dead key tile and the table's tail read none
    of them."""
    from distributed_pytorch_tpu.ops import flash_decode as fd
    nh, nkv, hs = shape
    bs = 8
    q, kp, vp, bt, kl, vl = _mk_chunk(T, n_max, bs, nh, nkv, hs, seed=off)
    call = fd.paged_flash_prefill
    if score is not None:
        # a jitted callee is cached by its shapes: a patched budget takes
        # the function under the jit
        monkeypatch.setattr(fd, "_CHUNK_SCORE_BYTES", score)
        call = jax.jit(call.__wrapped__,
                       static_argnames=("scale", "n_kv_heads", "interpret"))
    rows = T if real is None else real
    needed = -(-(off + rows) // bs)
    if real is not None:
        bt = bt.at[0, needed:].set(0)
        from distributed_pytorch_tpu.ops.block_pool import paged_gather
        kl, vl = (paged_gather(pool, bt, (nkv, hs)) for pool in (kp, vp))
    assert fd.paged_flash_prefill_usable(q, kp, vp, bt, nkv)
    keep = jnp.isin(jnp.arange(kp.shape[0]), bt[0, :needed])
    if real is not None:
        keep = keep.at[0].set(True)             # the pads' null block
    kp = jnp.where(keep[:, None, None], kp, jnp.nan)
    vp = jnp.where(keep[:, None, None], vp, jnp.inf)
    tq, group = fd._chunk_shape(T, nh // nkv, n_max, bs)
    assert (T // tq > 1) == (score is not None)
    out = call(q, kp, vp, bt, jnp.int32(off), scale=hs ** -0.5,
               n_kv_heads=nkv, interpret=True)
    ref = _naive_sdpa(q, kl, vl, scale=hs ** -0.5, q_offset=off)
    np.testing.assert_allclose(np.asarray(out)[:, :rows],
                               np.asarray(ref)[:, :rows],
                               atol=1e-5, rtol=1e-5)


# (T, rep, table width) of a chunk call at the accepted serving cells (a
# table is max_len / 128 + a chunk's blocks wide) -> (tq, group)
_CELL_CHUNKS = {
    "gpt2xl_serve_closed24": ((256, 1, 10), (256, 8)),
    "nemotron_h_serve_closed64": ((256, 16, 6), (256, 6)),
    "granite4h_serve_closed64": ((256, 4, 6), (256, 6)),
    "lfm2moe_serve_closed128": ((256, 4, 10), (256, 8)),
    "laguna_serve_closed64_long": ((1024, 6, 136), (512, 8)),
    "one_block_table": ((256, 4, 1), (256, 1)),
}


@pytest.mark.parametrize("cell", list(_CELL_CHUNKS))
def test_chunk_shape_at_the_cells(cell):
    """`_chunk_shape` on plain ints: the query tile divides the chunk in
    whole sublanes, the key tile is whole blocks of the table, its score
    tile and the whole step fit their budgets, and a table of one block
    gives the (whole chunk, 1 block) step."""
    from distributed_pytorch_tpu.compat import VMEM_LIMIT_BYTES
    from distributed_pytorch_tpu.ops import flash_decode as fd
    (T, rep, n_max), want = _CELL_CHUNKS[cell]
    tq, group = fd._chunk_shape(T, rep, n_max, 128)
    assert (tq, group) == want
    assert T % tq == 0 and tq % 8 == 0 and 1 <= group <= n_max
    assert tq * rep * group * 128 * 4 <= fd._CHUNK_SCORE_BYTES
    for hs in (64, 128):
        assert fd._chunk_vmem_bytes(tq * rep, group * 128, 128 // hs, 128,
                                    2, 2) <= VMEM_LIMIT_BYTES


def test_chunk_prefill_parity_int8():
    """int8 pools ride the chunk kernel's block-table index map; the
    in-kernel dequant owes the dequantized gathered oracle full parity
    (exact algebra, same as the decode kernel's contract)."""
    from distributed_pytorch_tpu.ops.block_pool import paged_gather
    from distributed_pytorch_tpu.ops.flash_decode import paged_flash_prefill
    from distributed_pytorch_tpu.ops.quant import dequantize_int8, quantize_kv
    T, n_max, bs, nh, nkv, hs = 16, 8, 8, 8, 4, 16
    q, kp, vp, bt, _, _ = _mk_chunk(T, n_max, bs, nh, nkv, hs, seed=3,
                                    merged=False)
    kq, ks_ = quantize_kv(kp)
    vq, vs = quantize_kv(vp)
    out = paged_flash_prefill(q, kq, vq, bt, jnp.int32(8),
                              scale=hs ** -0.5, k_scale=ks_, v_scale=vs,
                              interpret=True)
    kd = dequantize_int8(paged_gather(kq, bt), paged_gather(ks_, bt), q.dtype)
    vd = dequantize_int8(paged_gather(vq, bt), paged_gather(vs, bt), q.dtype)
    ref = _naive_sdpa(q, kd, vd, scale=hs ** -0.5, q_offset=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_chunk_prefill_trailing_blocks_fully_skipped():
    """Blocks past the chunk's last needed one must contribute nothing:
    the index-map clamp keeps the DMA on the last valid block, so poison
    beyond it cannot leak into the chunk's rows."""
    from distributed_pytorch_tpu.ops.flash_decode import paged_flash_prefill
    T, n_max, bs, nh, nkv, hs = 16, 8, 8, 4, 4, 8
    q, kp, vp, bt, _, _ = _mk_chunk(T, n_max, bs, nh, nkv, hs)
    off = 8                                      # rows [8, 24): blocks 0..2
    needed = {int(bt[0, j]) for j in range(3)}
    mask = ~jnp.isin(jnp.arange(kp.shape[0]), jnp.asarray(list(needed)))
    kp = jnp.where(mask[:, None, None], jnp.nan, kp)
    vp = jnp.where(mask[:, None, None], jnp.inf, vp)
    out = paged_flash_prefill(q, kp, vp, bt, jnp.int32(off),
                              scale=hs ** -0.5, n_kv_heads=nkv,
                              interpret=True)
    assert bool(jnp.isfinite(out).all())


def test_chunk_prefill_usable_gate_declines():
    from distributed_pytorch_tpu.ops.flash_decode import \
        paged_flash_prefill_usable
    q, kp, vp, bt, _, _ = _mk_chunk(16, 8, 8, 8, 4, 16)
    assert paged_flash_prefill_usable(q, kp, vp, bt, 4)
    # single-token (decode-shaped) query -> the decode kernel's job
    assert not paged_flash_prefill_usable(q[:, :1], kp, vp, bt, 4)
    # chunk not a sublane multiple
    assert not paged_flash_prefill_usable(q[:, :12], kp, vp, bt, 4)
    # batched chunks: one sequence at a time only
    q2 = jnp.concatenate([q, q], axis=0)
    assert not paged_flash_prefill_usable(q2, kp, vp, bt, 4)
    # block size the hardware cannot tile (9 rows)
    q3, kp3, vp3, bt3, _, _ = _mk_chunk(16, 8, 9, 8, 4, 16)
    assert not paged_flash_prefill_usable(q3, kp3, vp3, bt3, 4)
    # heads that straddle the 128-lane groups the grid is cut by (96 wide)
    q4, kp4, vp4, bt4, _, _ = _mk_chunk(16, 8, 8, 4, 4, 96)
    assert not paged_flash_prefill_usable(q4, kp4, vp4, bt4, 4)
    # live multi-device mesh -> gather + naive carries sharded decode
    from distributed_pytorch_tpu.parallel import context
    from distributed_pytorch_tpu.parallel.mesh import mesh_for
    with context.use_mesh(mesh_for("dp")):
        assert not paged_flash_prefill_usable(q, kp, vp, bt, 4)
    assert paged_flash_prefill_usable(q, kp, vp, bt, 4)
