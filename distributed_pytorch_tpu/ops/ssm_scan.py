"""The Mamba-2 state-space recurrence, in its two serving forms.

One head keeps a state h of (P, N): P the head size, N the state size.

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t (outer) B_t
    y_t = h_t C_t + D * x_t

with A < 0 a scalar a head, dt_t > 0 a scalar a head and step, B_t and C_t
(N,) vectors shared by the heads of a group. `ssm_step` is that line for
one token of every sequence in a batch (decode). `ssd_chunked` is the same
recurrence over a whole (padded) sequence in the chunked "state-space
dual" form: inside a chunk of Q steps the outputs are one masked (Q, Q)
product, and only the chunk boundaries are carried sequentially, so a
256-token prefill is 2 sequential steps and otherwise matmuls
(arXiv:2405.21060, section 6). A step with dt = 0 is the identity on the
state (decay 1, input 0): that is how a chunk's pad rows are kept out of
it, a recurrence has no null block to land them in.

Both keep the state in float32, and both keep it STATE-MAJOR: a slot's
state is (N, H * P), the state axis on the rows and every head's P values
side by side on the lanes (H * P is a multiple of 128 at every published
size; P alone is 64 in two of three). So `y = h C` is a sum over ROWS,
vector adds with no traffic between lanes, B and C scale whole rows, and
decay, dt x and D x are one lane vector a slot.

`ssm_step` sends the decode line to `ssm_step_kernel` where
`ssm_step_kernel_decline` finds nothing against it: one Pallas call,
`ssm_state_step`, that moves the state with DMAs of its own, IN TURNS: a
phase of slots (16 MB of state) is read while the phase before it is
worked on in place in VMEM, then that phase is written back alone, where
it came from (the state operand is aliased to the state result). Why turns
and not a BlockSpec pipeline: over 268 MB on a v5e, by the device's clock,
reads alone run at 92.2% of 819 GB/s and writes alone at 79.7%; a read and
a write in flight together, which is what a BlockSpec pipeline does (and
what XLA's fusion of this line got in the cells: 76-79%), at 80.0-80.5%;
in turns of 16 MB at 84.9% (PERF.md section 6, PRs 55-56). The arithmetic
hides under the reads, so the kernel runs at 83-84%: read its roofline
share against 85, not 100. `ssm_step_xla`, the same line in jax.numpy on
the same leaf, is the reference of the parity tests and carries every call
the gate declines, for one of six reasons: the backend is no TPU; the
state is not float32 `(S, N, H * P)`; N is no multiple of 8 (a sublane
tile); a group's lanes are no whole tiles of 128; a multi-device mesh is
live (GSPMD cannot partition a `pallas_call`); two phases and the blocks
of rows pass `compat.VMEM_LIMIT_BYTES`. The choice is from shapes and the
backend alone; which way a program went is in its `paths` line
(obs/paths.py, kind `ssm_step`).
`causal_conv` / `conv_step` are the depthwise width-K convolution in front
of it, with the last K - 1 inputs as its carried tail; three mixers share
them (models/ssm.py, models/shortconv.py, models/linear_attention.py).

Which recurrence lives where: THIS file is the scalar decay a head (Mamba-2:
a state of (N, H * P), one exp(dt A) a head and step). The matrix-valued
state with a decay a CHANNEL and the delta rule's write (KDA: a state of
(H, d_k, d_v)) is ops/delta_rule.py, whose step kernel takes these turns
through a ring of THREE buffers (its arithmetic outlasts a phase's read).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_pytorch_tpu.compat import tpu_compiler_params
from distributed_pytorch_tpu.obs import paths
from distributed_pytorch_tpu.ops.flash_decode import (_budget_decline,
                                                      _pick_block)

#: bytes of state a phase of the step kernel reads, works on and writes
#: back (two such buffers live in VMEM: turns of 16 MB read 84.9% of the
#: HBM peak, of 8 MB 84.0%, PR 55); the lanes one pass over a slot's rows
#: holds decay, dt x and the running sum of y for, and the rows one of its
#: steps takes: passes of 1,024 or 2,048 lanes and steps of 8 or 64 rows
#: time alike on the chip, and the wider ones are a third of the ops to
#: TRACE (1.1 s a program on the chip's host against 3.5-5.8, in `setup_s`)
_PHASE_BYTES = 16 << 20
_PASS_LANES = 2048
_ROWS_A_STEP = 64


def _heads(t: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    """(..., G, N) -> (..., H, N): a group's B or C serves H // G heads."""
    return jnp.repeat(t, n_heads // t.shape[-2], axis=-2)


def state_shape(n_heads: int, head_dim: int, n_state: int) -> tuple:
    """One slot's state, state-major: (N, H * P)."""
    return (n_state, n_heads * head_dim)


def ssm_step_xla(h, x, dt, A, B, C, D, live=None):
    """The decode line in jax.numpy (`ssm_step` has the shapes), every
    operand spread over the state's own rows and lanes INSIDE the one
    fusion (a group's B or C reaches its lanes through selects on the
    lane's number, not through an array of the state's size), so the state
    is read once, written in place and never laid out anew."""
    S, N, HP = h.shape
    H, P = x.shape[1:]
    G = B.shape[1]
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    group_of = (jnp.arange(HP) // (HP // G))[None, None, :]

    def lanes(t):                       # (S, H, P) -> a slot's lane vector
        return t.reshape(S, 1, HP)

    def rows(t):                        # (S, G, N) -> (S, N, lanes)
        t = t.astype(f32)
        out = t[:, 0, :, None]
        for g in range(1, G):
            out = jnp.where(group_of == g, t[:, g, :, None], out)
        return out

    decay = lanes(jnp.broadcast_to(jnp.exp(dt * A)[..., None], x.shape))
    h_new = decay * h + lanes(dt[..., None] * x) * rows(B)
    if live is not None:
        h_new = jnp.where(live[:, None, None], h_new, h)
    y = jnp.sum(h_new * rows(C), axis=1).reshape(S, H, P) + D[:, None] * x
    return y, h_new


def _step_shape(S: int, N: int, HP: int, G: int, lane: int) -> tuple:
    """(slots a phase, lanes a pass, rows a block) of the step kernel, from
    the call's shapes: a phase is the most whole slots that divide S within
    `_PHASE_BYTES` (one at least); a pass a share of ONE group's lanes (its
    rows take one B and one C) in whole lane tiles of `lane` (128 on the
    chip), 0 where there is no such split; the slots' rows of x, dt, B, C
    and y come in blocks of 8 (a sublane tile) that a phase divides or is
    made of, else all S at once."""
    k = max(1, min(S, _PHASE_BYTES // (4 * N * HP)))
    while S % k:
        k -= 1
    rows = k if k % 8 == 0 else 8 if 8 % k == 0 and S % 8 == 0 else S
    return (k, _pick_block(HP // G, _PASS_LANES, lane) if HP % G == 0 else 0,
            rows)


def _step_vmem_bytes(k: int, rows: int, N: int, HP: int, G: int) -> int:
    """Two phases of state; the blocks of x, dt and y rows, of B and C rows
    and the shared A and D rows (padded to a sublane tile), each twice."""
    return 2 * k * N * HP * 4 + 2 * 4 * (
        3 * rows * HP + 2 * rows * -(-G // 8) * 8 * N + 2 * 8 * HP)


def _slot_step(alive, hv, r, x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref,
               y_ref, *, group: int, lanes_a_pass: int):
    """One slot's decode line, in place in `hv` (N, H * P) of VMEM; the
    slot is row `r` of x, dt, y (lane vectors of H * P: dt, and the shared
    a and d, repeated over a head's lanes) and of b, c (G, N: a group a
    row). A pass takes `lanes_a_pass` lanes of one group through all N
    rows, `_ROWS_A_STEP` at a time: the rows' B and C are two columns
    broadcast along the lanes, decay and dt x two rows held for the pass,
    y's sum eight running rows added up at its end. A slot that is not
    `alive` keeps its rows, bit for bit."""
    N, HP = hv.shape
    f32 = jnp.float32
    row = pl.ds(r, 1)
    step = _pick_block(N, _ROWS_A_STEP, 8)
    # a group's row of B as a column: lanes to sublanes through the
    # diagonal of its broadcast, exact (every other term is a zero)
    eye = jax.lax.broadcasted_iota(jnp.int32, (N, N), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1)

    def column(ref, g):
        return jnp.sum(jnp.where(eye, ref[r, g:g + 1, :], 0.0), axis=1,
                       keepdims=True)

    for g in range(HP // group):
        bcol, ccol = column(b_ref, g), column(c_ref, g)
        for lo in range(g * group, (g + 1) * group, lanes_a_pass):
            lanes = slice(lo, lo + lanes_a_pass)
            x, dt = x_ref[row, lanes], dt_ref[row, lanes]
            decay, xdt = jnp.exp(dt * a_ref[:, lanes]), dt * x
            keep = jnp.broadcast_to(alive, (step, lanes_a_pass)) != 0
            acc = jnp.zeros((8, lanes_a_pass), f32)
            for n in range(0, N, step):
                rows = slice(n, n + step)
                hc = hv[rows, lanes]
                hn = jnp.where(keep, hc * decay + bcol[rows] * xdt, hc)
                hv[rows, lanes] = hn
                acc = acc + jnp.sum((hn * ccol[rows]).reshape(
                    step // 8, 8, lanes_a_pass), axis=0)
            y_ref[row, lanes] = jnp.sum(acc, axis=0, keepdims=True) \
                + d_ref[:, lanes] * x


def _step_kernel(live_ref, h_hbm, x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref,
                 o_hbm, y_ref, buf, sem, *, k: int, group: int,
                 lanes_a_pass: int):
    """Phase i of the call: the states of slots i k .. i k + k - 1. The
    state's DMAs are the kernel's own and take TURNS: phase i + 1 is read
    into the other buffer while phase i is worked on in place in its own,
    then phase i is written back alone (`h_hbm` and `o_hbm` are one
    buffer: a phase is read before any write reaches it)."""
    i, n = pl.program_id(0), pl.num_programs(0)
    b = jax.lax.rem(i, 2)

    def read(p, into):
        return pltpu.make_async_copy(h_hbm.at[pl.ds(p * k, k)], buf.at[into],
                                     sem.at[0])

    def write(p, out_of):
        return pltpu.make_async_copy(buf.at[out_of],
                                     o_hbm.at[pl.ds(p * k, k)], sem.at[1])

    @pl.when(i == 0)
    def _():
        read(0, 0).start()
        read(0, 0).wait()

    @pl.when(i + 1 < n)
    def _():
        read(i + 1, 1 - b).start()

    def slot(kk, carry):
        s = i * k + kk
        _slot_step(live_ref[s], buf.at[b, kk],
                   jax.lax.rem(s, x_ref.shape[0]), x_ref, dt_ref, a_ref,
                   d_ref, b_ref, c_ref, y_ref, group=group,
                   lanes_a_pass=lanes_a_pass)
        return carry

    jax.lax.fori_loop(0, k, slot, 0)

    @pl.when(i + 1 < n)
    def _():
        read(i + 1, 1 - b).wait()

    write(i, b).start()
    write(i, b).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_step_kernel(h, x, dt, A, B, C, D, live=None, *,
                    interpret: bool = False):
    """`ssm_step` as ONE Pallas call, `ssm_state_step`: grid (phases of
    slots,), the state left in HBM (`pl.ANY`), read once and written in
    place by the kernel's own DMAs; the slots' rows of x, dt and y (plain
    2-D arrays: what produces and consumes them keeps its layout), their
    B and C and the shared A and D rows come and go through BlockSpecs."""
    S, N, HP = h.shape
    H, P = x.shape[1:]
    G = B.shape[1]
    f32 = jnp.float32
    k, lanes_a_pass, rows = _step_shape(S, N, HP, G, 8 if interpret else 128)
    assert lanes_a_pass, (N, HP, G)
    live = jnp.ones((S,), jnp.int32) if live is None \
        else live.astype(jnp.int32)

    def lanes_of(t):                    # (H,) a head -> (1, H * P) a lane
        return jnp.repeat(t.astype(f32), P).reshape(1, HP)

    def slots(i, live_ref):
        return ((i * k) // rows, 0)

    def shared(i, live_ref):
        return (0, 0)

    row = pl.BlockSpec((rows, HP), slots)
    groups = pl.BlockSpec((rows, G, N), lambda i, live_ref: (
        (i * k) // rows, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    h_new, y = pl.pallas_call(
        functools.partial(_step_kernel, k=k, group=HP // G,
                          lanes_a_pass=lanes_a_pass),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S // k,),
            in_specs=[in_hbm, row, row, pl.BlockSpec((1, HP), shared),
                      pl.BlockSpec((1, HP), shared), groups, groups],
            out_specs=[in_hbm, row],
            scratch_shapes=[pltpu.VMEM((2, k, N, HP), f32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct(h.shape, f32),
                   jax.ShapeDtypeStruct((S, HP), f32)],
        input_output_aliases={1: 0},
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        name="ssm_state_step",
        interpret=interpret,
    )(live, h, x.astype(f32).reshape(S, HP),
      jnp.repeat(dt.astype(f32), P, axis=-1), lanes_of(A), lanes_of(D),
      B.astype(f32), C.astype(f32))
    return y.reshape(S, H, P), h_new


def ssm_step_kernel_decline(h, x, B, *, interpret: bool = False):
    """Why `ssm_step_kernel` cannot take this call (None = it can)."""
    if not interpret and jax.default_backend() != "tpu":
        return f"the {jax.default_backend()} backend is no TPU"
    if h.ndim != 3 or h.dtype != jnp.float32:
        return f"state {h.dtype}{list(h.shape)} is not float32 (S, N, H * P)"
    S, N, HP = h.shape
    G = B.shape[1]
    if N % 8 != 0:
        return f"a state of {N} rows is no multiple of 8"
    lane = 8 if interpret else 128
    k, lanes_a_pass, rows = _step_shape(S, N, HP, G, lane)
    if not lanes_a_pass:
        return (f"{HP} lanes over {G} groups are no whole tiles of {lane} "
                "a group")
    from distributed_pytorch_tpu.parallel import context
    mesh = context.get_mesh()
    if mesh is not None and any(s > 1 for s in mesh.devices.shape):
        return ("a live multi-device mesh (GSPMD cannot partition a "
                "pallas_call)")
    return _budget_decline(_step_vmem_bytes(k, rows, N, HP, G))


def ssm_step_kernel_usable(h, x, B) -> bool:
    return ssm_step_kernel_decline(h, x, B) is None


def ssm_step(h, x, dt, A, B, C, D, live=None):
    """One token. h (S, N, H * P) float32, state-major; x (S, H, P); dt
    (S, H) after the softplus; A, D (H,); B, C (S, G, N). Returns (y
    (S, H, P) float32, h'). Rows where `live` (S,) is False keep their
    state. A slot's y and h' depend on the slot's own operands and on the
    call's shapes alone, whichever path takes the call."""
    why = ssm_step_kernel_decline(h, x, B)
    if why is None:
        S, N, HP = h.shape
        paths.note("ssm_step", "ssm_state_step",
                   "state in place, %d slots a phase" % _step_shape(
                       S, N, HP, B.shape[1], 128)[0])
        return ssm_step_kernel(h, x, dt, A, B, C, D, live)
    paths.note("ssm_step", "xla", f"ssm_step_kernel_decline: {why}")
    return ssm_step_xla(h, x, dt, A, B, C, D, live)


def ssd_chunked(x, dt, A, B, C, D, h0=None, *, chunk: int = 128):
    """A whole sequence. x (B, T, H, P); dt (B, T, H) after the softplus,
    0 on pad rows; A, D (H,); B, C (B, T, G, N); h0 (B, N, H * P),
    state-major as `ssm_step` keeps it, or None for zeros. Returns (y
    (B, T, H, P) float32, h_T (B, N, H * P)). T is padded up to a multiple
    of the chunk here, with dt = 0."""
    Bb, T, H, P = x.shape
    Q = min(chunk, T)
    pad = (-T) % Q
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    B, C = B.astype(f32), C.astype(f32)
    if pad:
        x, dt, B, C = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                       for t in (x, dt, B, C))
    nc = (T + pad) // Q
    xc = x.reshape(Bb, nc, Q, H, P)
    dtc = dt.reshape(Bb, nc, Q, H)
    Bc = _heads(B, H).reshape(Bb, nc, Q, H, -1)
    Cc = _heads(C, H).reshape(Bb, nc, Q, H, -1)
    cum = jnp.cumsum(dtc * A, axis=2)                       # (B, c, Q, H)
    # inside a chunk: y_i += sum_{j<=i} exp(cum_i - cum_j) (C_i . B_j) dt_j x_j
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]     # (B, c, i, j, H)
    mask = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    L = jnp.where(mask, jnp.exp(jnp.where(mask, seg, 0.0)), 0.0)
    scores = jnp.einsum("bcihn,bcjhn->bcijh", Cc, Bc) * L
    xdt = xc * dtc[..., None]
    y = jnp.einsum("bcijh,bcjhp->bcihp", scores, xdt)
    # what each chunk adds to the state at its end, and the carried state
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)               # (B, c, Q, H)
    G, N = B.shape[-2:]
    HP, lanes_g = H * P, H * P // G
    h0 = None if h0 is None else h0.astype(f32)
    if P % 128:
        # a head's P is no whole lane tile (64 at two published sizes):
        # rows of (H, P) and rows of H * P lanes are two layouts, so the
        # products stay head-major and ONE slot's state is turned at the
        # two edges (31.4 ms a chunk-carrying granite program against 31.8
        # the other way, PR 55)
        add = jnp.einsum("bcjhn,bcjh,bcjhp->bchpn", Bc, to_end, xdt)
        total = jnp.exp(cum[:, :, -1, :])                   # (B, c, H)
        h = jnp.zeros((Bb, H, P, N), f32) if h0 is None \
            else h0.reshape(Bb, N, H, P).transpose(0, 2, 3, 1)
        enter = []
        for c in range(nc):              # the only sequential part: T / Q
            enter.append(h)
            h = total[:, c, :, None, None] * h + add[:, c]
        y = y + jnp.einsum("bcihn,bchpn->bcihp",
                           Cc * jnp.exp(cum)[..., None],
                           jnp.stack(enter, axis=1))
        y = y + D[:, None] * xc
        return y.reshape(Bb, nc * Q, H, P)[:, :T], \
            h.transpose(0, 3, 1, 2).reshape(Bb, N, HP)
    # state-major throughout, a group's lanes at a time (a group's B and C
    # are shared by its heads, so both products are plain matrices: B^T
    # (N, Q) @ w (Q, lanes) and C (Q, N) @ state (N, lanes)): 44.9 ms a
    # chunk-carrying falcon program against 46.2 head-major
    w = (xdt * to_end[..., None]).reshape(Bb, nc, Q, HP)
    total = jnp.broadcast_to(jnp.exp(cum[:, :, -1, :])[..., None],
                             (Bb, nc, H, P)).reshape(Bb, nc, 1, HP)
    Bg, Cg = B.reshape(Bb, nc, Q, G, N), C.reshape(Bb, nc, Q, G, N)
    from_state, h_end = [], []
    for g in range(G):
        lanes = slice(g * lanes_g, (g + 1) * lanes_g)
        add = jnp.einsum("bcjn,bcjl->bcnl", Bg[:, :, :, g], w[..., lanes])
        h = jnp.zeros((Bb, N, lanes_g), f32) if h0 is None \
            else h0[:, :, lanes]
        enter = []
        for c in range(nc):              # the only sequential part: T / Q
            enter.append(h)
            h = total[:, c, :, lanes] * h + add[:, c]
        from_state.append(jnp.einsum("bcin,bcnl->bcil", Cg[:, :, :, g],
                                     jnp.stack(enter, axis=1)))
        h_end.append(h)
    y = y + jnp.exp(cum)[..., None] * jnp.concatenate(
        from_state, axis=-1).reshape(Bb, nc, Q, H, P)
    y = y + D[:, None] * xc
    return y.reshape(Bb, nc * Q, H, P)[:, :T], \
        jnp.concatenate(h_end, axis=-1)


def causal_conv(u, w, b, tail=None):
    """Depthwise causal convolution over time. u (B, T, D); w (K, D), w[k]
    on the input K - 1 - k steps back; b (D,) or None for no bias; tail
    (B, K - 1, D) the inputs before u[:, 0] (zeros when None). Returns (out
    (B, T, D), the input with its tail in front (B, K - 1 + T, D))."""
    K = w.shape[0]
    if tail is None:
        tail = jnp.zeros((u.shape[0], K - 1, u.shape[2]), u.dtype)
    full = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    T = u.shape[1]
    out = sum(full[:, k:k + T] * w[k].astype(u.dtype) for k in range(K))
    return (out if b is None else out + b.astype(u.dtype)), full


def conv_step(u, w, b, tail, live=None):
    """One token of `causal_conv`. u (S, D); tail (S, K - 1, D). Returns
    (out (S, D), tail'). Rows where `live` is False keep their tail."""
    win = jnp.concatenate([tail.astype(u.dtype), u[:, None]], axis=1)
    out = jnp.einsum("skd,kd->sd", win, w.astype(u.dtype))
    if b is not None:
        out = out + b.astype(u.dtype)
    new_tail = win[:, 1:].astype(tail.dtype)
    if live is not None:
        new_tail = jnp.where(live[:, None, None], new_tail, tail)
    return out, new_tail
