"""Ask the chip's compiler, without the chip.

Every Pallas kernel the dispatchers can select on a TPU is compiled here
for a DESCRIBED v5e (`jax.experimental.topologies`, nothing attached) at
the flagship widths — 12 heads x 64, C=768, vocab 50304, 128-row KV
blocks — so what Mosaic refuses costs a test failure instead of a chip
call. Interpret mode (every other kernel test) runs none of this: it never
sees the 128-lane tile padding, the scoped-VMEM limit, or a relayout the
hardware has no instruction for. Before this file the flash backward, the
int8 contiguous decode and the 512-row chunk prefill all passed their
interpret tests and were refused by the compiler.

A compile that passes is not a chip run; numerics and times come from
`chip_smoke.py`.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
# nothing is attached, so nothing is contended: without this, test workers
# running side by side (xdist) collide on libtpu's one-process-per-chip
# lockfile and all but one abort
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from distributed_pytorch_tpu.obs import paths  # noqa: E402
from distributed_pytorch_tpu.ops import block_pool as bp  # noqa: E402
from distributed_pytorch_tpu.ops import delta_rule as dr  # noqa: E402
from distributed_pytorch_tpu.ops import flash_attention as fa  # noqa: E402
from distributed_pytorch_tpu.ops import flash_decode as fd  # noqa: E402
from distributed_pytorch_tpu.ops import grouped_matmul as gm  # noqa: E402
from distributed_pytorch_tpu.ops import latent_attention as la  # noqa: E402
from distributed_pytorch_tpu.ops import window_attention as wa  # noqa: E402

BF16, I8, F32, I32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32
NH, HS, C, V, BS = 12, 64, 768, 50304, 128     # flagship widths
SCALE = HS ** -0.5


@pytest.fixture(scope="module")
def v5e():
    """One described v5e chip, with the persistent compile cache off
    around the module: a device-free compile is written to the cache but
    cannot be read back without a chip (it would warn and recompile)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e chip here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _flash(B, T, grad, nh=NH, hs=HS):
    shapes = [((B, T, nh, hs), BF16)] * 3

    def fwd(q, k, v):
        return fa.flash_attention(q, k, v, scale=SCALE)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(F32).sum(),
                        argnums=(0, 1, 2))(q, k, v)
    return (bwd if grad else fwd), shapes


def _decode(B, q8):
    kv = ((B, 1024, NH, HS), I8 if q8 else BF16)
    shapes = [((B, NH, HS), BF16), kv, kv, ((B,), I32)]
    if q8:
        shapes += [((B, 1024, NH, 1), F32)] * 2
        return (lambda q, k, v, cl, ks, vs: fd.flash_decode(
            q, k, v, cl, scale=SCALE, k_scale=ks, v_scale=vs)), shapes
    return (lambda q, k, v, cl: fd.flash_decode(
        q, k, v, cl, scale=SCALE)), shapes


def _pool(n_blocks, nh, q8):
    """One paged k/v pool leaf as models/attention.py declares it: int8
    codes keep the head axis, float pools merge the heads into lanes."""
    if q8:
        return ((n_blocks, BS, nh, HS), I8)
    return ((n_blocks, BS, bp.kv_lanes(nh, HS)), BF16)


def _paged_decode(B, q8, nh=NH, nkv=0, hs=HS, width=8):
    """`nkv` (float pools): fewer kv heads than query heads, of `hs`."""
    if nkv:
        pool = ((B * 8, BS, bp.kv_lanes(nkv, hs)), BF16)
        shapes = [((B, nh, hs), BF16), pool, pool, ((B, width), I32),
                  ((B,), I32)]
        return (lambda q, k, v, bt, cl: fd.paged_flash_decode(
            q, k, v, bt, cl, scale=hs ** -0.5, n_kv_heads=nkv)), shapes
    pool = _pool(B * 8, nh, q8)
    shapes = [((B, nh, HS), BF16), pool, pool, ((B, 8), I32), ((B,), I32)]
    if q8:
        shapes += [((B * 8, BS, nh, 1), F32)] * 2
        return (lambda q, k, v, bt, cl, ks, vs: fd.paged_flash_decode(
            q, k, v, bt, cl, scale=SCALE, k_scale=ks, v_scale=vs)), shapes
    return (lambda q, k, v, bt, cl: fd.paged_flash_decode(
        q, k, v, bt, cl, scale=SCALE, n_kv_heads=nh)), shapes


def _paged_prefill(T, q8, nh=NH, nkv=0, hs=HS, width=8):
    if nkv:
        pool = ((64, BS, bp.kv_lanes(nkv, hs)), BF16)
        shapes = [((1, T, nh, hs), BF16), pool, pool, ((1, width), I32),
                  ((), I32)]
        return (lambda q, k, v, bt, o: fd.paged_flash_prefill(
            q, k, v, bt, o, scale=hs ** -0.5, n_kv_heads=nkv)), shapes
    pool = _pool(64, nh, q8)
    shapes = [((1, T, nh, HS), BF16), pool, pool, ((1, 8), I32), ((), I32)]
    if q8:
        shapes += [((64, BS, nh, 1), F32)] * 2
        return (lambda q, k, v, bt, o, ks, vs: fd.paged_flash_prefill(
            q, k, v, bt, o, scale=SCALE, k_scale=ks, v_scale=vs)), shapes
    return (lambda q, k, v, bt, o: fd.paged_flash_prefill(
        q, k, v, bt, o, scale=SCALE, n_kv_heads=nh)), shapes


def _window_decode(B, nh, ring, nkv=8, hs=128):
    leaf = ((B, ring, bp.kv_lanes(nkv, hs)), BF16)
    return (lambda q, k, v, pos: wa.window_flash_decode(
        q, k, v, pos, window=ring, scale=hs ** -0.5, n_kv_heads=nkv)), [
            ((B, nh, hs), BF16), leaf, leaf, ((B,), I32)]


def _window_prefill(T, nh, ring, nkv=8, hs=128):
    keys = ((ring + T, bp.kv_lanes(nkv, hs)), BF16)
    return (lambda q, k, v, off: wa.window_flash_prefill(
        q, k, v, off, window=ring, scale=hs ** -0.5, n_kv_heads=nkv)), [
            ((1, T, nh, hs), BF16), keys, keys, ((), I32)]


def _latent(chunk, nh=32, lc=512, dn=128, dr=64, dv=128, width=136):
    """JoyAI-LLM-Flash's published latent attention (PR 59): 32 heads of
    128 + 64 / 128 over rows of 512 + 64 in 640 lanes, 64 slots at a table
    136 blocks wide; a decode batch, or a chunk of `chunk` rows."""
    pool = ((8200, BS, la.row_lanes(lc, dr)), BF16)
    scale = (dn + dr) ** -0.5
    if not chunk:
        return (lambda q, p, bt, cl: la.latent_flash_decode(
            q, p, bt, cl, scale=scale, lc=lc)), [
                ((64, nh, pool[0][2]), BF16), pool, ((64, width), I32),
                ((64,), I32)]
    return (lambda qn, qr, p, w, bt, o: la.latent_flash_prefill(
        qn, qr, p, w, bt, o, scale=scale)), [
            ((1, chunk, nh, dn), BF16), ((1, chunk, nh, dr), BF16), pool,
            ((lc, nh, dn + dv), BF16), ((1, width), I32), ((), I32)]


def _gmm(grad):
    # the bench MoE's widths (C=768, 8 experts incl. 1 shared, top-2
    # routed, swiglu up 1024 -> fused fc_out 2048); 2048 tokens keep the
    # compile at seconds — the kernels' tiles do not depend on the count
    N, E, U = 2048, 8, 1024
    shapes = [((N, C), BF16), ((N, 2), I32), ((N, 2), F32),
              ((E, C, 2 * U), BF16), ((E, U, C), BF16)]

    def fwd(x, i, g, fc, pj):
        return gm.grouped_dispatch(x, i, g, fc, pj, non_linearity="swiglu",
                                   n_shared=1, interpret=False)

    def bwd(x, i, g, fc, pj):
        return jax.grad(lambda a, b, c: fwd(a, i, g, b, c).astype(F32).sum(),
                        argnums=(0, 1, 2))(x, fc, pj)
    return (bwd if grad else fwd), shapes


# id -> (builder, kernels the compiled text must hold)
CASES = {
    "flash_fwd_8x1024": (lambda: _flash(8, 1024, False), ["flash_fwd"]),
    "flash_bwd_8x1024": (lambda: _flash(8, 1024, True),
                         ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]),
    "flash_bwd_16x1024": (lambda: _flash(16, 1024, True),
                          ["flash_bwd_dq", "flash_bwd_dkv"]),
    # beyond the XLA memory guard every `auto` call takes the kernel
    "flash_fwd_1x8192": (lambda: _flash(1, 8192, False), ["flash_fwd"]),
    "flash_bwd_1x8192": (lambda: _flash(1, 8192, True),
                         ["flash_bwd_dq", "flash_bwd_dkv"]),
    # a full-lane head: 8 heads of 128 (twice the tile bytes of 64-wide)
    # two q tiles: the slabbed diagonal body and the whole masked body
    # under `pl.when` in ONE kernel, the smallest shape that holds both
    "flash_bwd_4x2048": (lambda: _flash(4, 2048, True),
                         ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]),
    "flash_bwd_2x1024_8x128": (lambda: _flash(2, 1024, True, 8, 128),
                               ["flash_fwd", "flash_bwd_dq",
                                "flash_bwd_dkv"]),
    "flash_decode_bf16": (lambda: _decode(8, False), ["flash_decode"]),
    "flash_decode_int8_32slots": (lambda: _decode(32, True),
                                  ["flash_decode_q8"]),
    "paged_decode_bf16": (lambda: _paged_decode(8, False),
                          ["paged_flash_decode"]),
    "paged_decode_int8": (lambda: _paged_decode(32, True),
                          ["paged_flash_decode_q8"]),
    "paged_prefill_256": (lambda: _paged_prefill(256, False),
                          ["paged_flash_prefill"]),
    "paged_prefill_512": (lambda: _paged_prefill(512, False),
                          ["paged_flash_prefill"]),
    "paged_prefill_int8_256": (lambda: _paged_prefill(256, True),
                               ["paged_flash_prefill_q8"]),
    # gpt2-xl's 25 heads x 64: 1600 lanes padded to 1664, the last lane
    # group half pad
    "paged_decode_bf16_25x64": (lambda: _paged_decode(24, False, 25),
                                ["paged_flash_decode"]),
    "paged_decode_int8_25x64": (lambda: _paged_decode(24, True, 25),
                                ["paged_flash_decode_q8"]),
    "paged_prefill_256_25x64": (lambda: _paged_prefill(256, False, 25),
                                ["paged_flash_prefill"]),
    "paged_prefill_int8_256_25x64": (lambda: _paged_prefill(256, True, 25),
                                     ["paged_flash_prefill_q8"]),
    "gmm_fwd": (lambda: _gmm(False), ["gmm_fwd"]),
    "gmm_bwd": (lambda: _gmm(True), ["gmm_fwd", "gmm_dx", "gmm_dw"]),
    # a chip's share of the experts at the published widths of the
    # patterned configuration: 2688 -> 1856 -> 2688, 64 of 128 held, top 6,
    # a decode batch and a prefill chunk
    "held_experts_64tok": (lambda: _held_experts(64),
                           ["expert_matmul_up", "expert_matmul_down"]),
    "held_experts_256tok": (lambda: _held_experts(256),
                            ["expert_matmul_up", "expert_matmul_down"]),
    # the gated share at Granite 4.0-H's published widths: 4096 -> 2 x 768
    # -> 4096, 36 of 72 held, top 10; the up stack as published, (expert,
    # 1536, 4096), and a kernel name of its own
    "gated_experts_64tok": (lambda: _held_experts(64, **GRANITE_EXPERTS),
                            ["expert_matmul_gated_up",
                             "expert_matmul_down"]),
    "gated_experts_256tok": (lambda: _held_experts(256, **GRANITE_EXPERTS),
                             ["expert_matmul_gated_up",
                              "expert_matmul_down"]),
    # every expert held at LFM2-24B-A2B's published widths: 2048 -> 2 x
    # 1536 -> 2048, 64 of 64, top 4: a plain program's call of 128 decode
    # rows and a chunk-carrying program's one call over both row sets
    "lfm2_experts_128tok": (lambda: _held_experts(128, **LFM2_EXPERTS),
                            ["expert_matmul_gated_up",
                             "expert_matmul_down"]),
    "lfm2_experts_256+128tok": (lambda: _held_experts((256, 128),
                                                      **LFM2_EXPERTS),
                                ["expert_matmul_gated_up",
                                 "expert_matmul_down"]),
    # Laguna-S-2.1's published attention (PR 49): 8 KV heads of 128 in 1,024
    # lanes; a sliding layer's 72 query heads over a ring of 512 rows a
    # slot, a full layer's 48 over the pools at a table 136 blocks wide;
    # a decode batch of 64 and a chunk of 1,024 rows
    "window_decode_64x72x128": (lambda: _window_decode(64, 72, 512),
                                ["window_flash_decode"]),
    "window_prefill_1024x72x128": (lambda: _window_prefill(1024, 72, 512),
                                   ["window_flash_prefill"]),
    "paged_decode_bf16_48x128": (lambda: _paged_decode(64, False, 48, 8,
                                                       128, 136),
                                 ["paged_flash_decode"]),
    "paged_prefill_1024_48x128": (lambda: _paged_prefill(1024, False, 48, 8,
                                                         128, 136),
                                  ["paged_flash_prefill"]),
    "latent_decode_64x32x640": (lambda: _latent(0), ["latent_flash_decode"]),
    "latent_prefill_1024x32": (lambda: _latent(1024),
                               ["latent_flash_prefill"]),
    # Ling-3.0-flash's chunk (PR 62): 256 rows under a table 42 blocks wide
    "latent_prefill_256x32": (lambda: _latent(256, width=42),
                              ["latent_flash_prefill"]),
    # Ling-3.0-flash's published KDA state (PR 62): 32 heads of a 128 x 128
    # float32 state a slot, 192 slots a call, 8 slots (16 MB) a phase, three
    # phases in VMEM (PR 63: 49.5 MiB by the gate's count, under the 64 MiB)
    "kda_step_192x32x128x128": (lambda: _kda_step(192),
                                ["kda_state_step"]),
}


def _kda_step(n, H=32, d=128):
    shapes = [((n, H, d, d), F32)] + [((n, H, d), F32)] * 4 \
        + [((n, H), F32), ((n,), jnp.bool_)]
    return (lambda S, q, k, v, g, b, live: dr.kda_step_kernel(
        S, q, k, v, g, b, live)), shapes


GRANITE_EXPERTS = dict(C=4096, F=768, held=36, k=10, n_routed=72, gated=True)
LFM2_EXPERTS = dict(C=2048, F=1536, held=64, k=4, n_routed=64, gated=True)


def _held_experts(rows, C=2688, F=1856, held=64, k=6, n_routed=128,
                  gated=False):
    """`rows`: the call's tokens, or a tuple of row sets (`cuts`)."""
    cuts = rows if isinstance(rows, tuple) else None
    N = sum(cuts) if cuts else rows
    shapes = [((N, C), BF16), ((N, k), I32), ((N, k), F32),
              ((held, (2 if gated else 1) * F, C), BF16),
              ((held, F, C), BF16)]
    return (lambda x, i, g, wu, wd: gm.held_experts_ffn(
        x, i, g, wu, wd, first=0, gated=gated, n_routed=n_routed,
        cuts=cuts, interpret=False)), shapes


def _compile(fn, shapes, chip):
    avals = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    return jax.jit(fn).lower(*avals).compile()


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_compiles_for_v5e(case, v5e):
    build, want = CASES[case]
    fn, shapes = build()
    census = paths.kernel_census(_compile(fn, shapes, v5e).as_text())
    for name in want:
        assert census.get(name), (
            f"{case}: compiled, but no tpu_custom_call named {name!r} in "
            f"the program (census {census})")
        # a chunk call is ONE kernel of its name a layer, whatever its grid
        # step holds: the benchmark's roofline reader divides the summed
        # device time of the ops of that name by their count
        if name in ("paged_flash_prefill", "window_flash_prefill",
                    "latent_flash_prefill"):
            assert census[name] == 1, (case, census)
    if case == "latent_decode_64x32x640":
        # what the gate sums for the walk Mosaic took the body at
        # (`_latent_walk`: (8, 16) here) is inside the limit it is handed
        q, pool, bt, _ = (jax.ShapeDtypeStruct(s, d) for s, d in shapes)
        assert la._decode_vmem_bytes(q, pool, bt, 512) \
            < fd.VMEM_LIMIT_BYTES
    if case.startswith("latent_prefill"):
        # and what the chunk gate sums for the two-halves body (PR 65: a
        # front's score tile, values and row maxima wait in VMEM for the
        # next step's back) is inside the limit Mosaic took the body at
        qn, _, pool, w, bt, _ = (jax.ShapeDtypeStruct(s, d)
                                 for s, d in shapes)
        assert la._prefill_vmem_bytes(qn, pool, w, bt) < fd.VMEM_LIMIT_BYTES


# ---------------------------------------------------------------------------
# what `auto` selects for a training call: the flash kernels, no score tensor
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(16, 1024, 12, 64), (2, 1024, 25, 64)],
                         ids=["gpt2_b16", "gpt2xl_b2"])
def test_auto_trains_on_the_flash_kernels(shape, v5e, monkeypatch):
    """`jax.grad` through `sdpa(impl='auto')` at the train cell's shape
    and at gpt2-xl's: the dispatcher (told it is on a TPU; the compile is
    for the described chip) notes the kernel, the program holds all three
    and no [B, nh, T, T] score tensor in any dtype."""
    from distributed_pytorch_tpu.ops import attention_core as core
    monkeypatch.setattr(core, "_on_tpu", lambda: True)
    B, T, nh, hs = shape

    def bwd(q, k, v):
        return jax.grad(lambda *a: core.sdpa(*a, impl="auto").astype(
            F32).sum(), argnums=(0, 1, 2))(q, k, v)

    paths.reset()
    text = _compile(bwd, [(shape, BF16)] * 3, v5e).as_text()
    assert paths.choices()["attention"] == (
        "pallas flash (attn_impl=auto; 4 causal slabs of 256 rows a diagonal "
        "tile: 62.5% of the score square computed)")
    census = paths.kernel_census(text)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert census.get(name) == 1, census
    assert f"[{B},{nh},{T},{T}]" not in text, "a score tensor in the program"


# ---------------------------------------------------------------------------
# the FFN's exact gelu under a gradient: the erfc expansion once a layer
# ---------------------------------------------------------------------------

FFN_ACT = "16,1024,3072"            # the train cell's activation, B x T x up


def _computations(text):
    """{computation: its instructions' lines} of a compiled program's text;
    the entry computation under "ENTRY"."""
    bodies, name = {}, None
    for ln in text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", ln)
        if head:
            name = "ENTRY" if head.group(1) else head.group(2)
            bodies[name] = []
        elif name and ln.startswith("}"):
            name = None
        elif name:
            bodies[name].append(ln)
    return bodies


@pytest.fixture(scope="module")
def ffn_step_fusions(v5e):
    """Value-and-grad of one FFN at the train cell's shapes (16 x 1024 x
    768 -> 3072, bf16 compute over float32 weights) compiled for the
    described chip, read a fused computation at a time: name -> (ops of
    each kind over the activation's shape, the entry fusion's results)."""
    from distributed_pytorch_tpu.models.mlp import mlp_apply

    def loss(x, w_fc, w_proj, t):
        y = mlp_apply(x, w_fc.astype(BF16), w_proj.astype(BF16), "gelu")
        return jnp.mean((y.astype(F32) - t) ** 2)

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                    [((16, 1024, C), BF16), ((C, 3072), F32),
                     ((3072, C), F32), ((16, 1024, C), F32)], v5e).as_text()
    bodies = _computations(text)
    fusions = {}
    for ln in bodies["ENTRY"]:
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) fusion\(.*"
                     r"calls=%?([\w.\-]+)", ln)
        if m:
            results = re.findall(r"(\w+)\[([\d,]*)\]", m.group(1))
            fusions[m.group(2)] = {
                **{op: sum(bool(re.search(rf"= \w+\[{FFN_ACT}\]\S* {op}\(",
                                          b)) for b in bodies[m.group(2)])
                   for op in ("exponential", "divide")},
                "dtypes": [d for d, _ in results],
                "wrote": [d for d, shape in results if shape == FFN_ACT]}
    return fusions


def test_the_erfc_expansion_is_evaluated_once_a_layer(ffn_step_fusions):
    """Left to autodiff the compiler cloned the expansion (its two divides
    and an exponential) into the down-projection's forward, weight-gradient
    and dgrad fusions: three evaluations a layer, each at the vector unit's
    pace (PERF.md section 6, PR 46). The rule leaves ONE, in the
    up-projection's epilogue; backward's own exponential (the density's,
    no divide) rides in the dgrad. The whole step is read with the recipe
    of PERF.md section 7."""
    fs = ffn_step_fusions
    erfc = [n for n, f in fs.items() if f["divide"]]
    assert len(erfc) == 1, fs
    assert (fs[erfc[0]]["exponential"], fs[erfc[0]]["divide"]) == (1, 2), fs
    assert sum(bool(f["exponential"]) for f in fs.values()) == 2, fs


def test_the_up_projection_writes_the_pair_and_nothing_float32(
        ffn_step_fusions):
    """What forward keeps for backward is two bf16 arrays a layer (the
    pre-activation and its erfc), both written by the fusion that holds
    the expansion; no float32 array of the activation's shape reaches
    HBM, and no bit mask is kept."""
    fs = ffn_step_fusions
    assert [f["wrote"] for f in fs.values() if f["divide"]] == [
        ["bf16", "bf16"]], fs
    # the pair and the dgrad's result: nothing else of that shape, in
    # any dtype
    assert sorted(d for f in fs.values() for d in f["wrote"]) == [
        "bf16"] * 3, fs
    assert not any("u16" in f["dtypes"] for f in fs.values()), fs


# ---------------------------------------------------------------------------
# the head's cross-entropy under a gradient: dx and dW in the forward scan
# ---------------------------------------------------------------------------

_LOSS_SHAPES = [((16, 1024, C), BF16), ((V, C), BF16), ((16, 1024), I32)]


def test_the_loss_builds_each_logits_block_once(v5e):
    """Value-and-grad of the chunked loss at the train cell's shapes (16 x
    1024 x 768 bf16, a bf16 embedding of 50,304 rows, 8 chunks of 128):
    ONE loop with three head-sized matmuls (logits, dx, dW). Left to
    autodiff under `jax.checkpoint` it was two loops and four, every
    float32 `[16,128,50304]` block built and reduced twice (PERF.md
    section 6, PR 47), in 496,329,216 B of temp as ISSUE 47 read it."""
    from distributed_pytorch_tpu.ops.losses import fused_cross_entropy

    def value_and_grad(x, emb, t):
        return jax.value_and_grad(
            lambda a, e: fused_cross_entropy(a, e, t), argnums=(0, 1))(
            x, emb)

    compiled = _compile(value_and_grad, _LOSS_SHAPES, v5e)
    text = compiled.as_text()
    assert len(re.findall(r" while\(", text)) == 1
    # the loss has no other matmul: every convolution is the head's
    head = [ln for ln in text.splitlines() if " convolution(" in ln]
    assert len(head) == 3, head
    assert all("/while/body/" in ln for ln in head), head
    assert f"f32[16,1024,{V}]" not in text, "the whole logits in the program"
    assert compiled.memory_analysis().temp_size_in_bytes <= 496_329_216


def test_the_models_loss_call_runs_the_rule_in_pr_47s_temp(v5e):
    """What `LLM.__call__` calls (`tied_head_loss`, 'fused', chunk 0 =
    auto) under a gradient at the train cell's shapes: the census names the
    rule, and the program's temporaries stay where PR 47 left them
    (463,027,712 B: `dx`, `dW` and one float32 block)."""
    from distributed_pytorch_tpu.ops.losses import tied_head_loss

    def value_and_grad(x, emb, t):
        return jax.value_and_grad(
            lambda a, e: tied_head_loss(a, e, t, impl="fused", chunk=0),
            argnums=(0, 1))(x, emb)

    paths.reset()
    compiled = _compile(value_and_grad, _LOSS_SHAPES, v5e)
    assert paths.choices()["loss"] == (
        "fused, gradients in the forward scan (8 chunks of 128 tokens)")
    paths.reset()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert abs(temp / 463_027_712 - 1) <= 0.05, temp


def test_the_undifferentiated_loss_is_one_matmul_a_chunk(v5e):
    """Evaluation and the benchmark's check take no gradient: one loop, one
    head matmul a chunk, nothing kept."""
    from distributed_pytorch_tpu.ops.losses import tied_head_loss

    paths.reset()
    text = _compile(
        lambda x, emb, t: tied_head_loss(x, emb, t, impl="fused", chunk=0),
        _LOSS_SHAPES, v5e).as_text()
    assert paths.choices()["loss"] == (
        "fused, plain scan (8 chunks of 128 tokens)")
    paths.reset()
    assert len(re.findall(r" while\(", text)) == 1
    head = [ln for ln in text.splitlines() if " convolution(" in ln]
    assert len(head) == 1 and "/while/body/" in head[0], head
    assert f"f32[16,1024,{V}]" not in text, "the whole logits in the program"


# ---------------------------------------------------------------------------
# the paged pools are never copied: one layout from the donated argument
# through the write and the kernel
# ---------------------------------------------------------------------------

#: (heads, blocks, slots, table width): gpt2-xl's serving cell and the
#: file's flagship
POOL_GEOMETRIES = {"25x64": (25, 200, 24, 10), "12x64": (12, 64, 8, 8)}
CHUNK, SPEC_T = 256, 5


def _serving_step(kind, nh):
    """The cache half of one compiled serving step at `nh` heads of 64:
    the write(s) into donated k/v pools, then the reader.
    'decode' = T == 1 row write + paged_flash_decode (`step`);
    'fused' = whole-block chunk write + paged_flash_prefill, then
    'decode' on the pools that leaves (`fused_step`);
    'spec' = the per-slot window write + gather (`spec_step`'s path)."""
    def decode(kp, vp, q, k, v, bt, pos):
        kp = bp.paged_update(kp, k, pos, bt)
        vp = bp.paged_update(vp, v, pos, bt)
        y = fd.paged_flash_decode(q[:, 0], kp, vp, bt, pos + 1, scale=SCALE,
                                  n_kv_heads=nh)
        return kp, vp, y

    def fused(kp, vp, q, k, v, bt, pos, qc, kc, vc, off):
        kp = bp.paged_update(kp, kc, off, bt[:1])
        vp = bp.paged_update(vp, vc, off, bt[:1])
        yc = fd.paged_flash_prefill(qc, kp, vp, bt[:1], off, scale=SCALE,
                                    n_kv_heads=nh)
        return decode(kp, vp, q, k, v, bt, pos) + (yc,)

    def spec(kp, vp, q, k, v, bt, pos):
        kp = bp.paged_update(kp, k, pos, bt)
        vp = bp.paged_update(vp, v, pos, bt)
        kl = bp.paged_gather(kp, bt, (nh, HS))
        vl = bp.paged_gather(vp, bt, (nh, HS))
        s = jnp.einsum("btnh,bsnh->bnts", q, kl)
        return kp, vp, jnp.einsum("bnts,bsnh->btnh", s, vl)
    return {"decode": decode, "fused": fused, "spec": spec}[kind]


def _serving_shapes(kind, nh, n_blocks, n_slots, width):
    pool = ((n_blocks, BS, bp.kv_lanes(nh, HS)), BF16)
    T = 1
    if kind == "spec":
        # a 2-block table: the gathered logical views (a temporary by
        # design) stay far below a pool, so a pool-sized one would show
        T, width = SPEC_T, 2
    row = ((n_slots, T, nh, HS), BF16)
    shapes = [pool, pool, row, row, row, ((n_slots, width), I32),
              ((n_slots,), I32)]
    if kind == "fused":
        shapes += [((1, CHUNK, nh, HS), BF16)] * 3 + [((), I32)]
    return shapes


def _pool_copies(text, pool_shape, dtype="bf16"):
    """The compiled program's `copy` ops that produce a whole bf16 pool (or
    a whole leaf of another `dtype`)."""
    pool_type = "%s[%s]" % (dtype, ",".join(map(str, pool_shape)))
    return [ln.strip()[:160] for ln in text.splitlines()
            if f"= {pool_type}" in ln and " copy(" in ln]


@pytest.mark.parametrize("kind,kernels", [
    ("decode", ["paged_flash_decode"]),
    ("fused", ["paged_flash_prefill", "paged_flash_decode"]),
    ("spec", [])])
@pytest.mark.parametrize("geometry", list(POOL_GEOMETRIES))
def test_no_whole_pool_copy_in_a_serving_step(geometry, kind, kernels, v5e):
    """With the pools donated, the compiled program writes them in place
    and hands the kernels the same buffer: no `copy` of a pool's shape,
    less temporary memory than one pool, pools aliased input to output.
    (With (n_blocks, 128, 25, 64) pools the decode case held 4 such
    copies and 0.586 GiB of temporaries: 104 of a 124.6 ms step.)"""
    nh, n_blocks, n_slots, width = POOL_GEOMETRIES[geometry]
    shapes = _serving_shapes(kind, nh, n_blocks, n_slots, width)
    avals = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in shapes]
    compiled = jax.jit(_serving_step(kind, nh),
                       donate_argnums=(0, 1)).lower(*avals).compile()
    text = compiled.as_text()
    copies = _pool_copies(text, shapes[0][0])
    assert not copies, f"whole-pool copies in the {kind} program: {copies}"
    pool_bytes = 2 * n_blocks * BS * bp.kv_lanes(nh, HS)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < pool_bytes, (
        f"{mem.temp_size_in_bytes} bytes of temporaries, a pool is "
        f"{pool_bytes}")
    assert mem.alias_size_in_bytes >= 2 * pool_bytes, (
        f"pools not aliased in to out: {mem.alias_size_in_bytes} aliased")
    census = paths.kernel_census(text)
    for name in kernels:
        assert census.get(name), (kind, census)


#: the serving cells' decode calls: slots, heads, kv heads, head size, table
#: width (gpt2-xl; nemotron's 2 kv heads under rep 16; granite's 8 under 4;
#: LFM2's 8 x 64 under rep 4 at 128 slots)
CELL_DECODES = {"24x25x64_w8": (24, 25, 25, 64, 8),
                "64x2kvx128_rep16_w4": (64, 32, 2, 128, 4),
                "64x8kvx128_rep4_w4": (64, 32, 8, 128, 4),
                "128x8kvx64_rep4_w10": (128, 32, 8, 64, 10)}


@pytest.mark.parametrize("cell", list(CELL_DECODES))
def test_one_decode_call_a_layer_inside_the_gates_vmem(cell, v5e,
                                                       monkeypatch):
    """A layer's write + decode read at each serving cell's shape: ONE
    custom call named `paged_flash_decode` (`paged_decode_roofline` divides
    one layer's bytes by the mean time of the calls of that name), the
    pools read where the write left them, and the kernel compiles with the
    scoped-VMEM limit set to what its gate counts for it
    (`_walk_vmem_bytes`): the gate never lets through what Mosaic would
    refuse."""
    n_slots, nh, nkv, hs, width = CELL_DECODES[cell]
    n_blocks = n_slots * width + 1
    pool = ((n_blocks, BS, bp.kv_lanes(nkv, hs)), BF16)
    row = ((n_slots, 1, nkv, hs), BF16)
    shapes = [pool, pool, ((n_slots, 1, nh, hs), BF16), row, row,
              ((n_slots, width), I32), ((n_slots,), I32)]
    avals = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in shapes]
    assert fd.paged_flash_decode_decline(avals[2], avals[0], avals[1],
                                         avals[5], nkv) is None
    need = fd._walk_vmem_bytes(avals[2], avals[0], avals[5], nkv)
    assert need < fd.VMEM_LIMIT_BYTES // 4
    monkeypatch.setattr(
        fd, "tpu_compiler_params",
        lambda **kw: fd.pltpu.CompilerParams(vmem_limit_bytes=need, **kw))
    decode = fd.paged_flash_decode.__wrapped__      # traced under the patch

    def step(kp, vp, q, k, v, bt, pos):
        kp = bp.paged_update(kp, k, pos, bt)
        vp = bp.paged_update(vp, v, pos, bt)
        return kp, vp, decode(q[:, 0], kp, vp, bt, pos + 1,
                              scale=hs ** -0.5, n_kv_heads=nkv)

    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*avals).compile()
    text = compiled.as_text()
    assert paths.kernel_census(text).get("paged_flash_decode") == 1
    assert not _pool_copies(text, pool[0])
    pool_bytes = 2 * n_blocks * BS * bp.kv_lanes(nkv, hs)
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


# a plain program's call, a chunk alone, a fused program's merged call
EXPERT_CALLS = pytest.mark.parametrize("n_tokens", [64, 256, (256, 64)],
                                       ids=["64", "256", "256+64"])


EXPERT_WIDTHS = pytest.mark.parametrize(
    "widths", [{}, GRANITE_EXPERTS], ids=["relu2_64of128", "gated_36of72"])
_expert_programs = {}


def _compiled_experts(n_tokens, widths, chip):
    """(compiled `held_experts_ffn`, its text, its operands' shapes): one
    compile a case, read by both tests below. The temporaries' bound rides
    along: a 256-row chunk at top 10 packs 4,864 rows of 4096, the merged
    call 5,504, ~100 MB of packed buffers, the price of the tile
    (held_tile_rows)."""
    key = (n_tokens, bool(widths))
    if key not in _expert_programs:
        fn, shapes = _held_experts(n_tokens, **widths)
        compiled = _compile(fn, shapes, chip)
        assert compiled.memory_analysis().temp_size_in_bytes < (
            160 if widths and n_tokens != 64 else 64) * 2 ** 20
        _expert_programs[key] = compiled.as_text(), shapes
    return _expert_programs[key]


@EXPERT_WIDTHS
@EXPERT_CALLS
def test_no_copy_of_an_expert_stack(n_tokens, widths, v5e):
    """The held experts' two stacks reach the kernels as they lie: no
    `copy` of a stack's shape and next to no temporaries. (With the up
    matrices held (64, 2688, 1856), in by out, the device laid the
    parameter out minor-in-2688 and every call began with a 639 MB
    relayout copy; out by in it has none. The gated stack is (36, 1536,
    4096) as published: out by in already.)"""
    text, shapes = _compiled_experts(n_tokens, widths, v5e)
    for stack in {shapes[3][0], shapes[4][0]}:
        stack = "bf16[%s]" % ",".join(map(str, stack))
        copies = [ln.strip()[:160] for ln in text.splitlines()
                  if f"= {stack}" in ln and " copy(" in ln]
        assert not copies, copies


@EXPERT_WIDTHS
@EXPERT_CALLS
def test_the_combine_gathers_a_tokens_rows_and_scatters_none(n_tokens,
                                                             widths, v5e):
    """The combine is a token's gather of its k rows of the packed float32
    result and a chain of adds: no scatter into an (n, C) float32 result is
    left (a row scatter-add ran at a seventh to a quarter of the row
    gather's rate on the chip, PR 40), the gather is k-major, (k, n, C),
    so no (n, k, C) array exists whose k axis the device would pad to a
    tile (10 -> 16, behind a relayout), and its rows cost no temporaries
    beyond the packed buffers."""
    text, shapes = _compiled_experts(n_tokens, widths, v5e)
    C, k = shapes[0][0][1], shapes[1][0][1]
    scatters = [ln.strip()[:120] for ln in text.splitlines()
                if re.search(rf"= f32\[\d+,{C}\]\S* scatter\(", ln)]
    assert not scatters, scatters
    for n in n_tokens if isinstance(n_tokens, tuple) else (n_tokens,):
        assert f"f32[{n},{k},{C}]" not in text
        assert re.search(rf"= f32\[{n * k},{C}\]\S* fusion\(.*"
                         r"moe_combine/gather", text), (n, k, C)


def _entry_ops_under(text, scope):
    """[(opcode, result type, opcodes inside)] of the ENTRY computation's
    instructions whose `op_name` holds `scope`; `opcodes inside` a fusion
    are those of the computation it calls and of the ones that one calls."""
    comps = _computations(text)
    inst = re.compile(
        r"\s+(?:ROOT )?%?[\w.\-]+ = (\(.*?\)|\S+) ([\w\-]+)\(")

    def inside(ln):
        found = set()
        for name in re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", ln):
            for inner in comps.get(name, []):
                m = inst.match(inner)
                if m:
                    found |= {m.group(2)} | inside(inner)
        return found

    ops = []
    for ln in comps["ENTRY"]:
        m = inst.match(ln)
        if m and f"/{scope}/" in ln:
            ops.append((m.group(2), m.group(1), inside(ln)))
    return ops


# the ceiling: what a call holds today, the row gather and the scatter among
# them (17 a decode call, 20 a merged one; the parent held 23 to 26, counted
# the same way, AND a sort and a loop)
PACK_FUSIONS = 20


@EXPERT_WIDTHS
@pytest.mark.parametrize("n_tokens", [64, (256, 64)], ids=["64", "256+64"])
def test_the_packing_walks_no_index_but_one_scatters(n_tokens, widths, v5e):
    """Under `moe_pack` a call holds no sort, no loop, ONE scatter (the
    packing's inverse), the row gather and no other gather beyond two: the
    chip walks an int32 gather or scatter index by index (35 to 65 us each
    at 10,880 assignments, my chip run, PR 58); eight to ten of them, a
    sort and a loop stood between the router and the first byte of expert
    weights, and each had come in as one convenient `.at[]` or `[order]`.
    The small dense fusions that replaced them read 0.01 to 0.6 us each;
    their count has a ceiling all the same, so that the chain cannot grow
    back."""
    text, shapes = _compiled_experts(n_tokens, widths, v5e)
    C = shapes[0][0][1]
    ops = _entry_ops_under(text, "moe_pack")
    assert ops, "no op of the program carries the scope"
    walked = [(op, typ) for op, typ, inside in ops
              if ({op} | inside) & {"sort", "while"}]
    assert not walked, walked
    fusions = [(typ, inside) for op, typ, inside in ops if op == "fusion"]
    scatters = [typ for typ, inside in fusions if "scatter" in inside]
    gathers = [typ for typ, inside in fusions if "gather" in inside]
    rows = [typ for typ in gathers if re.match(rf"bf16\[\d+,{C}\]", typ)]
    assert len(scatters) <= 1, scatters
    assert len(rows) == 1 and len(gathers) - 1 <= 2, gathers
    assert len(fusions) <= PACK_FUSIONS, (len(fusions), fusions)
    loose = [op for op, _, _ in ops if op in ("scatter", "gather")]
    assert not loose, loose


@pytest.mark.parametrize("rows", [192, 256])
def test_the_group_limit_sorts_nothing(rows, v5e):
    """The group-limited sigmoid router at the Ling cell's widths (2,560 ->
    512 experts, 8 groups of which 4 are kept, top 8) and its two row
    counts: ONE sort in the program, the final top 8 over the 512 lanes,
    and no loop. Until PR 69 it held three: `f32[rows,8,64]`, a whole sort
    of each group's 64 lanes for its two largest (0.13 to 0.18 ms a call,
    5.3% of the cell's device time: ledger, PR 67), and `f32[rows,8]` for
    the best groups."""
    from distributed_pytorch_tpu.models.mlp import route_sigmoid
    text = _compile(
        lambda x, gate, bias: route_sigmoid(x, gate, bias, 8, 2.5, 8, 4),
        [((rows, 2560), BF16), ((2560, 512), F32), ((512,), F32)],
        v5e).as_text()
    sorted_ = re.findall(r" = \((\w+\[[\d,]*\])\S*, .*?\) sort\(", text)
    assert sorted_ == [f"f32[{rows},512]"], sorted_
    assert not re.search(r" while\(", text)
    assert "/route_groups/" in text


@pytest.mark.parametrize("line, H, P, G, N", [
    ("xla", 64, 64, 8, 128), ("xla", 128, 64, 1, 128),
    ("kernel", 64, 64, 8, 128), ("kernel", 128, 64, 1, 128),
    ("kernel", 32, 128, 2, 256)],
    ids=["64heads_8groups", "128heads_1group", "kernel_64heads_8groups",
         "kernel_128heads_1group", "kernel_32heads_of_128_state_256"])
def test_the_one_token_recurrence_updates_its_state_in_place(line, H, P, G,
                                                             N, v5e):
    """The state-space decode step at the published sizes (64 slots x 64
    heads x 64 x 128 float32 = 134 MB a layer; at 128 heads and one group,
    and at 32 heads of 128 over a state of 256 in two groups, 268 MB, 4.19
    MB a slot) with the state donated, by the jax.numpy line and by the
    kernel: aliased in to out, no copy of it, temporaries far below one
    state; the kernel ONE custom call that leaves the state in HBM and
    moves it itself (two phases of 16 MB in VMEM)."""
    from distributed_pytorch_tpu.ops import ssm_scan
    S = 64
    shapes = [((S, N, H * P), F32), ((S, H, P), BF16), ((S, H), F32),
              ((H,), F32), ((S, G, N), BF16), ((S, G, N), BF16), ((H,), F32),
              ((S,), jnp.bool_)]
    avals = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in shapes]
    step = ssm_scan.ssm_step_xla if line == "xla" \
        else ssm_scan.ssm_step_kernel
    compiled = jax.jit(step, donate_argnums=(0,)).lower(*avals).compile()
    state_bytes = 4 * S * H * P * N
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < state_bytes // 4, mem.temp_size_in_bytes
    text = compiled.as_text()
    assert not _pool_copies(text, (S, N, H * P), "f32")
    assert paths.kernel_census(text) == (
        {} if line == "xla" else {"ssm_state_step": 1})
    if line == "kernel":
        assert ssm_scan.ssm_step_kernel_decline(*avals[:2], avals[4],
                                                interpret=True) is None


#: one block of the two cells whose largest op is the recurrence, at the
#: published widths: Falcon-H1's 'P' (32 heads of 128 over a state of 256,
#: GQA 20 over 4 beside it), granite's 'M' (128 heads of 64, one group)
STATE_BLOCKS = {
    "falcon_P": dict(n_embd=5120, layer_pattern="P", pos_emb="rope",
                     rope_pairing="half", n_head=20, n_kv_heads=4,
                     head_dim=128, ssm_heads=32, ssm_head_dim=128,
                     ssm_groups=2, ssm_state=256, ssm_chunk=128),
    "granite_M": dict(n_embd=4096, layer_pattern="M", pos_emb="none",
                      n_head=32, n_kv_heads=8, head_dim=128, ssm_heads=128,
                      ssm_head_dim=64, ssm_groups=1, ssm_state=128,
                      ssm_chunk=256)}


@pytest.mark.parametrize("program", ["step", "fused_step"])
@pytest.mark.parametrize("block", list(STATE_BLOCKS))
def test_the_engines_step_programs_hold_the_state_kernel(block, program, v5e,
                                                         monkeypatch):
    """The engine's own step functions over ONE block at 64 slots, the
    cache tree donated, compiled for the described chip (the gate told it
    is on a TPU): the program holds one `ssm_state_step`, the state leaf
    is aliased in to out, no `copy` makes an array of its size (the chunk
    path of a fused step hands ONE slot's state in and out), and the
    `paths` note names the kernel and its phase."""
    from distributed_pytorch_tpu.config import LLMConfig
    from distributed_pytorch_tpu.engine import decode as dec
    from distributed_pytorch_tpu.models.gpt import LLM, init_paged_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = LLMConfig(vocab_size=1024, block_size=4096, n_layer=1, attn="gqa",
                    attn_bias=False, tie_head=False, non_linearity="swiglu",
                    up_dim=1024, ssm_conv=4, **STATE_BLOCKS[block])
    model = LLM(cfg, compute_dtype=BF16, attn_impl="auto", param_dtype=BF16)
    n_slots, chunk, width = 64, 256, 6

    def sds(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=v5e)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, I32, sharding=v5e)

    key = jax.random.PRNGKey(0)
    variables = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda k: model.init({"params": k}, jnp.zeros((1, 8), I32)), key))
    caches = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: init_paged_cache(cfg, 264, BS, dtype=BF16, n_slots=n_slots)))
    slot = caches[0]           # a 'P' block's slot also holds its pools
    state = slot.get("slot_state", slot)["ssm"]
    assert state.dtype == F32 and state.shape == (
        n_slots, cfg.ssm_state, cfg.ssm_heads * cfg.ssm_head_dim)

    def sample(logits, rng):
        return jnp.argmax(logits, axis=-1).astype(I32)

    args = [variables, caches, i32(n_slots), i32(n_slots),
            jax.ShapeDtypeStruct((n_slots,), jnp.bool_, sharding=v5e),
            i32(n_slots, width), sds(key), i32(), None]
    if program == "step":
        fn = dec.make_step_fn(model, sample)
    else:
        fn = dec.make_fused_step_fn(model, sample, n_slots, width)
        args += [i32(1, chunk), i32(), i32(), i32(1),
                 jax.ShapeDtypeStruct((), jnp.bool_, sharding=v5e)]
    paths.reset()
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    assert paths.choices()["ssm_step"] == \
        "ssm_state_step (state in place, 4 slots a phase)"
    text = compiled.as_text()
    assert paths.kernel_census(text).get("ssm_state_step") == 1
    state_bytes = 4 * state.size
    assert compiled.memory_analysis().alias_size_in_bytes >= state_bytes
    assert compiled.memory_analysis().temp_size_in_bytes < state_bytes // 4
    assert not _pool_copies(text, state.shape, "f32")


@pytest.mark.parametrize("program", ["step", "fused_step"])
def test_the_gated_delta_trees_step_programs_compile(program, v5e,
                                                     monkeypatch):
    """One period G E * E of the Qwen3-Next tree at its published mixer
    widths (32 value heads over 16 key heads of 128; 16 query heads over 2
    KV heads of 256 lanes, a gate a channel; 8 experts of 512 held of 64)
    through the engine's own step functions, 8 slots under a block table of
    264 blocks (32,768 + a chunk of 1,024), the cache tree donated: both
    programs compile for the described chip, the 'G' block steps its state
    with `kda_state_step` in place, the '*' block reads its 512-lane pool
    with `paged_flash_decode` and, in a chunk-carrying program,
    `paged_flash_prefill` (whose step the gate has to shrink at 8 x 256
    lanes: `_chunk_shape`), and nothing falls back to a gather."""
    from distributed_pytorch_tpu.config import LLMConfig
    from distributed_pytorch_tpu.engine import decode as dec
    from distributed_pytorch_tpu.models.gpt import LLM, init_paged_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = LLMConfig(
        vocab_size=1024, block_size=32768, n_embd=2048, n_layer=4,
        layer_pattern="GE*E", pos_emb="rope", rope_theta=1e7,
        rope_pairing="half", rotary_frac=0.25, norm_eps=1e-6,
        norm_zero_centred=True, tie_head=False, attn="gqa", n_head=16,
        n_kv_heads=2, head_dim=256, qk_norm=True, attn_gate="channel",
        attn_bias=False, non_linearity="swiglu", up_dim=512,
        shared_up_dim=512, n_exp=65, n_shared=1, n_act=11,
        router="softmax_topk", shared_gate=True, experts_held=(0, 8),
        gdn_heads=32, gdn_key_heads=16, gdn_head_dim=128, gdn_conv=4)
    model = LLM(cfg, compute_dtype=BF16, attn_impl="auto", param_dtype=BF16)
    n_slots, chunk, width = 8, 1024, 264

    def sds(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=v5e)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, I32, sharding=v5e)

    key = jax.random.PRNGKey(0)
    variables = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda k: model.init({"params": k}, jnp.zeros((1, 8), I32)), key))
    caches = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: init_paged_cache(cfg, 1 + n_slots * 256, BS, dtype=BF16,
                                 n_slots=n_slots)))
    state, pool = caches[0]["state"], caches[2]["k"]
    assert state.dtype == F32 and state.shape == (n_slots, 32, 128, 128) \
        and pool.shape == (1 + n_slots * 256, BS, 512)

    def sample(logits, rng):
        return jnp.argmax(logits, axis=-1).astype(I32)

    args = [variables, caches, i32(n_slots), i32(n_slots),
            jax.ShapeDtypeStruct((n_slots,), jnp.bool_, sharding=v5e),
            i32(n_slots, width), sds(key), i32(), None]
    kernels = {"kda_state_step": 1, "paged_flash_decode": 1}
    if program == "step":
        fn = dec.make_step_fn(model, sample)
    else:
        fn = dec.make_fused_step_fn(model, sample, n_slots, width)
        args += [i32(1, chunk), i32(), i32(), i32(1),
                 jax.ShapeDtypeStruct((), jnp.bool_, sharding=v5e)]
        kernels["paged_flash_prefill"] = 1
    paths.reset()
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    chosen = paths.choices()
    assert chosen["kda_step"].startswith("kda_state_step (state in place")
    assert not any("gather" in v for v in chosen.values()), chosen
    if program == "fused_step":
        assert chosen["gdn_chunk"].startswith("xla_wy (a decay a head")
    text = compiled.as_text()
    census = paths.kernel_census(text)
    assert {k: census.get(k) for k in kernels} == kernels, census
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        4 * state.size + 2 * 2 * pool.size
    assert not _pool_copies(text, state.shape, "f32") \
        and not _pool_copies(text, pool.shape)


def test_gates_decline_what_the_compiler_refuses(v5e):
    """The other direction: where Mosaic refuses a kernel, its usable gate
    must already say no — so the dispatcher never sends that shape, and
    `attn_impl='pallas'` there is an error naming the gate, not a compile
    failure half a minute into a run."""
    # a head dim whose single tile step busts the scoped-VMEM limit the
    # kernel hands Mosaic
    wide = [((1, 1024, 1, 8192), BF16)] * 3
    q = jax.ShapeDtypeStruct(*wide[0])
    assert not fa.flash_attention_usable(q, q, q)
    assert "VMEM" in fa.flash_attention_decline(q, q, q)
    with pytest.raises(Exception, match="vmem|VMEM"):
        _compile(_flash_wide_bwd, wide, v5e)
    # and at the flagship the gate and the compiler agree the other way
    q = jax.ShapeDtypeStruct((8, 1024, NH, HS), BF16)
    assert fa.flash_attention_usable(q, q, q)


def _flash_wide_bwd(q, k, v):
    return jax.grad(lambda *a: fa.flash_attention(
        *a, scale=1.0, block_h=1).astype(F32).sum(), argnums=(0, 1, 2))(
            q, k, v)
