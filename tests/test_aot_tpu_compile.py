"""Ask the chip's compiler, without the chip.

Every Pallas kernel the dispatchers can select on a TPU is compiled here
for a DESCRIBED v5e (`jax.experimental.topologies`, nothing attached) at
the flagship widths — 12 heads x 64, C=768, vocab 50304, 128-row KV
blocks — so what Mosaic refuses costs a test failure instead of a chip
call. Interpret mode (every other kernel test) runs none of this: it never
sees the 128-lane tile padding, the scoped-VMEM limit, or a relayout the
hardware has no instruction for. Before this file the flash backward, the
CE backward, the int8 contiguous decode and the 512-row chunk prefill all
passed their interpret tests and were refused by the compiler.

A compile that passes is not a chip run; numerics and times come from
`chip_smoke.py`.
"""

import os

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
from distributed_pytorch_tpu.ops import flash_attention as fa  # noqa: E402
from distributed_pytorch_tpu.ops import flash_decode as fd  # noqa: E402
from distributed_pytorch_tpu.ops import fused_ce  # noqa: E402
from distributed_pytorch_tpu.ops import grouped_matmul as gm  # noqa: E402

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


def _flash(B, T, grad):
    shapes = [((B, T, NH, HS), BF16)] * 3

    def fwd(q, k, v):
        return fa.flash_attention(q, k, v, scale=SCALE)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(F32).sum(),
                        argnums=(0, 1, 2))(q, k, v)
    return (bwd if grad else fwd), shapes


def _ce(grad):
    shapes = [((16, 1024, C), BF16), ((V, C), BF16), ((16, 1024), I32)]

    def fwd(x, e, t):
        return fused_ce.pallas_cross_entropy(x, e, t)

    def bwd(x, e, t):
        return jax.grad(lambda a, b: fwd(a, b, t), argnums=(0, 1))(x, e)
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


def _paged_decode(B, q8):
    pool = ((B * 8, BS, NH, HS), I8 if q8 else BF16)
    shapes = [((B, NH, HS), BF16), pool, pool, ((B, 8), I32), ((B,), I32)]
    if q8:
        shapes += [((B * 8, BS, NH, 1), F32)] * 2
        return (lambda q, k, v, bt, cl, ks, vs: fd.paged_flash_decode(
            q, k, v, bt, cl, scale=SCALE, k_scale=ks, v_scale=vs)), shapes
    return (lambda q, k, v, bt, cl: fd.paged_flash_decode(
        q, k, v, bt, cl, scale=SCALE)), shapes


def _paged_prefill(T, q8):
    pool = ((64, BS, NH, HS), I8 if q8 else BF16)
    shapes = [((1, T, NH, HS), BF16), pool, pool, ((1, 8), I32), ((), I32)]
    if q8:
        shapes += [((64, BS, NH, 1), F32)] * 2
        return (lambda q, k, v, bt, o, ks, vs: fd.paged_flash_prefill(
            q, k, v, bt, o, scale=SCALE, k_scale=ks, v_scale=vs)), shapes
    return (lambda q, k, v, bt, o: fd.paged_flash_prefill(
        q, k, v, bt, o, scale=SCALE)), shapes


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
    # the shape attn_impl='auto' itself sends to the kernel (> 4096 keys)
    "flash_fwd_1x8192": (lambda: _flash(1, 8192, False), ["flash_fwd"]),
    "flash_bwd_1x8192": (lambda: _flash(1, 8192, True),
                         ["flash_bwd_dq", "flash_bwd_dkv"]),
    "ce_fwd": (lambda: _ce(False), ["ce_fwd"]),
    "ce_bwd": (lambda: _ce(True), ["ce_fwd", "ce_bwd_dx", "ce_bwd_dw"]),
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
    "gmm_fwd": (lambda: _gmm(False), ["gmm_fwd"]),
    "gmm_bwd": (lambda: _gmm(True), ["gmm_fwd", "gmm_dx", "gmm_dw"]),
}


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


def test_gates_decline_what_the_compiler_refuses(v5e):
    """The other direction: where Mosaic refuses a kernel, its usable gate
    must already say no — so the dispatcher never sends that shape, and
    `attn_impl='pallas'` there is an error naming the gate, not a compile
    failure half a minute into a run."""
    # (1) slab layout at the flagship's 64-wide heads: the in-VMEM head
    # split has no lowering below a full 128-lane head
    B, T = 2, 1024
    slab = fa._slab_lse_for(NH, NH, HS)
    assert not fa.slab_attention_usable(B, T, T, NH, NH, HS, BF16)
    with pytest.raises(Exception, match="shape cast|Mosaic"):
        _compile(lambda q, k, v, s: slab(q, k, v, s, SCALE, 256, 512, False,
                                         True, 0.0),
                 [((B, T, NH * HS), BF16)] * 3 + [((2,), I32)], v5e)
    assert fa.slab_attention_usable(B, T, T, 8, 8, 128, BF16)  # and compiles
    _compile(lambda q, k, v: fa.flash_attention(q, k, v, scale=SCALE,
                                                layout="slab"),
             [((B, T, 8, 128), BF16)] * 3, v5e)
    # (2) rows layout at a head dim whose single tile step busts the
    # scoped-VMEM limit the kernel hands Mosaic
    wide = [((1, 512, 1, 8192), BF16)] * 3
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
