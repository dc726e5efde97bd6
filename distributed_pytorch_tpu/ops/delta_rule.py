"""The gated delta rule with a decay a channel (KDA, arXiv 2510.26692
section 3), in its two serving forms.

One head keeps a state S of (d_k, d_v), float32, per sequence, zero at its
start. For a token with key k and query q (d_k,), value v (d_v,), log decay
g (d_k,) in (`lower_bound`, 0) and write strength beta in (0, 1):

    S' = Diag(exp(g)) S                      every key channel decays alone
    S  = S' + beta k (v - S'^T k)^T          the delta rule on what is left
    o  = S^T q

`kda_step` is those three lines for one token of every slot (decode): the
state is read once and written once, in place. It sends the call to
`kda_step_kernel` where `kda_step_kernel_decline` finds nothing against it:
ONE Pallas call, `kda_state_step`: the state stays in HBM and moves by the
kernel's own DMAs IN TURNS through a RING OF THREE phase buffers in VMEM.
While a phase of slots is worked on in place, the next phase is read under
the first half of its slots and the phase before it is written back under
the second half: a phase's arithmetic (~7 vector ops an element: longer than
either DMA, shorter than both) hides under a read AND a write, and no DMA
waits for the vector unit. (`ssm_state_step`, ops/ssm_scan.py, whose few ops
an element fit under the read alone, keeps two buffers and writes a phase
back alone: under that schedule ~10 us of this kernel's arithmetic would
stand bare in every phase.) What a head needs as a COLUMN over the state's
rows (exp(g), k, beta k, q: d_k values each) comes in as one (d_k, 4 H)
block a slot, heads along the lanes, transposed outside the kernel: a column
is then one lane of it, broadcast along the lanes, and both sums over d_k
are sums over ROWS, vector adds with no traffic between lanes.
`kda_step_xla`, the same three lines in jax.numpy, is the twin of the parity
tests and runs every call the gate declines (the backend is no TPU; the
state is not float32 (S, H, d_k, d_v); d_k is no multiple of 8 or d_v no
whole lane tile; a multi-device mesh is live; the ring's three phases pass
the VMEM budget).

`kda_chunk` is the same recurrence over a prefill chunk's T rows of ONE
sequence, from the slot's state at the chunk's start to the state at its
end, in the chunked (WY / UT) form. Rows are taken in sub-chunks of C = 16.
With Gamma_i the running sum of g inside a sub-chunk, S_0 the state at its
start and w_i = v_i - S'_i^T k_i the value the delta rule writes:

    (I + A) W = V - (K * exp(Gamma)) S_0,
        A_ij = beta_j sum_c k_ic k_jc exp(Gamma_ic - Gamma_jc),  j < i
    O = (Q * exp(Gamma)) S_0 + (P (.) beta) W,
        P_ij = sum_c q_ic k_jc exp(Gamma_ic - Gamma_jc),         j <= i
    S_C = Diag(exp(Gamma_C)) S_0 + (K * exp(Gamma_C - Gamma) * beta)^T W

(I + A) is unit lower triangular: its inverse is a FORWARD SUBSTITUTION of
C - 1 steps, made for every sub-chunk and head at once; only the last three
lines run sub-chunk after sub-chunk (T / C steps of four small matmuls).
A and P factor exp(Gamma_i - Gamma_j) into a decay exp(Gamma_i - Gamma_m)
and an INVERSE decay exp(Gamma_m - Gamma_j) about the sub-chunk's middle
row m: over at most 8 rows a side the exponent stays within 8 x
|lower_bound| = 40, far inside float32's 88 (the bound of the gate is what
makes this hold; `config.LLMConfig` asserts 16 x |lower_bound| < 88), and no
intermediate is larger than (T, H, d_k). Everything is float32 with
`Precision.HIGHEST`. A pad row has g = 0 and beta = 0: it decays nothing
and writes nothing. Whether the chunk form deserves a kernel of its own is
the next `perf_opt`'s to say from `kda_chunk_roofline.ling`; the choice
between paths is from shapes and the backend alone, and which way a program
went is in its `paths` line (obs/paths.py, kinds `kda_step`, `kda_chunk`).

The same rule with ONE log decay a head (Gated DeltaNet, arXiv 2412.06464;
models/linear_attention.py GatedDeltaNet) is the case g constant over a
head's channels. One token: `kda_step` as it is, the decay broadcast over
the channels by the caller (the kernel reads exp(g) a channel either way).
A chunk: `gdn_chunk`, whose decay is UNBOUNDED below (-exp(A_log)
softplus(.)): the inversion about a middle row above would overflow, and is
not needed, because a scalar decay comes out of the sum over the channels
(`gdn_chunk` has the lines; `paths` kind `gdn_chunk`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_pytorch_tpu.compat import tpu_compiler_params
from distributed_pytorch_tpu.obs import paths
from distributed_pytorch_tpu.ops.flash_decode import _budget_decline

#: bytes of state a phase of the step kernel reads, works on and writes
#: back (ops/ssm_scan.py measured the turns)
_PHASE_BYTES = 16 << 20
#: phase buffers in VMEM: one read into, one worked on, one written from
_RING = 3
#: rows of a sub-chunk of the chunked form: 16 x |lower_bound| < 88
SUB_CHUNK = 16
_HI = jax.lax.Precision.HIGHEST


def state_shape(n_heads: int, head_dim: int) -> tuple:
    """One slot's state: (H, d_k, d_v), d_k = d_v."""
    return (n_heads, head_dim, head_dim)


def kda_step_xla(S, q, k, v, g, beta, live=None):
    """The decode line in jax.numpy (`kda_step` has the shapes): products
    and sums over rows, no matmul (a TPU would round a float32 matmul's
    operands)."""
    f32 = jnp.float32
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
    Sd = jnp.exp(g)[..., None] * S
    u = v - jnp.sum(Sd * k[..., None], axis=-2)
    Sn = Sd + (beta[..., None] * k)[..., None] * u[..., None, :]
    if live is not None:
        Sn = jnp.where(live[:, None, None, None], Sn, S)
    return jnp.sum(Sn * q[..., None], axis=-2), Sn


def _step_slots(S: int, H: int, dk: int, dv: int) -> int:
    """Slots a phase of the step kernel: the most whole slots that divide
    S within `_PHASE_BYTES` (one at least). The slots' columns, v and o
    come in blocks of as many: a block cuts the slot axis alone, which no
    tile covers."""
    k = max(1, min(S, _PHASE_BYTES // (4 * H * dk * dv)))
    while S % k:
        k -= 1
    return k


def _step_vmem_bytes(k: int, H: int, dk: int, dv: int) -> int:
    """The ring's three phases of state; the blocks of columns (lanes in
    whole tiles), of v and of o, each twice."""
    return _RING * k * H * dk * dv * 4 + 2 * 4 * k * (
        dk * -(-4 * H // 128) * 128 + 2 * -(-H // 8) * 8 * dv)


def _slot_step(hv, r, cols_ref, v_ref, o_ref, *, H: int):
    """One live slot's decode line, in place in `hv` (H, d_k, d_v) of
    VMEM; the slot is entry `r` of v and o (H, d_v: a head a row, so the
    dynamic index is on an untiled axis) and of the columns (d_k, 4 H):
    lanes [exp(g) | k | beta k | q], a head each."""
    for h in range(H):
        def col(j):
            return cols_ref[r, :, j * H + h:j * H + h + 1]      # (d_k, 1)
        sd = hv[h] * col(0)
        u = v_ref[r, h:h + 1, :] - jnp.sum(sd * col(1), axis=0,
                                           keepdims=True)
        sn = sd + col(2) * u
        hv[h] = sn
        o_ref[r, h:h + 1, :] = jnp.sum(sn * col(3), axis=0, keepdims=True)


def _step_kernel(live_ref, s_hbm, cols_ref, v_ref, o_hbm, o_ref, buf, sem,
                 *, k: int, H: int):
    """Phase i of the call: the states of slots i k .. i k + k - 1, worked
    on in place in buffer i % 3 of the ring. Phase i + 1 is read into the
    next buffer under the first half of the slots; at the TURN (slot
    k // 2) that read is waited for and phase i - 1 starts back out of the
    third buffer, under the second half, and is waited for at the step's
    end: one DMA at a time, and none waits for the arithmetic. The edges:
    the first phase has nothing to write, so its read has all k slots over
    it; the last has nothing to read, so the write behind it starts at its
    first slot, and the phase itself leaves after its last (`s_hbm` and
    `o_hbm` are one buffer)."""
    i, n = pl.program_id(0), pl.num_programs(0)
    b = jax.lax.rem(i, _RING)
    ahead = jax.lax.rem(i + 1, _RING)
    behind = jax.lax.rem(i + _RING - 1, _RING)
    more, begun = i + 1 < n, i >= 1
    turn_at = jnp.where(begun, jnp.where(more, k // 2, 0), k)

    def read(p, into):
        return pltpu.make_async_copy(s_hbm.at[pl.ds(p * k, k)], buf.at[into],
                                     sem.at[0])

    def write(p, out_of):
        return pltpu.make_async_copy(buf.at[out_of],
                                     o_hbm.at[pl.ds(p * k, k)], sem.at[1])

    @pl.when(i == 0)
    def _():
        read(0, 0).start()
        read(0, 0).wait()

    @pl.when(more)
    def _():
        read(i + 1, ahead).start()

    def slot(kk, carry):
        @pl.when(kk == turn_at)
        def _():
            @pl.when(more)
            def _():
                read(i + 1, ahead).wait()
            write(i - 1, behind).start()

        alive = live_ref[i * k + kk] != 0

        @pl.when(alive)
        def _():
            _slot_step(buf.at[b, kk], kk, cols_ref, v_ref, o_ref, H=H)

        @pl.when(jnp.logical_not(alive))
        def _():
            # a dead slot keeps its state, bit for bit; its output is zeros
            o_ref[kk] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, k, slot, 0)

    @pl.when(jnp.logical_and(more, jnp.logical_not(begun)))
    def _():
        read(i + 1, ahead).wait()

    @pl.when(begun)
    def _():
        write(i - 1, behind).wait()

    @pl.when(jnp.logical_not(more))
    def _():
        write(i, b).start()
        write(i, b).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_step_kernel(S, q, k, v, g, beta, live=None, *,
                    interpret: bool = False):
    """`kda_step` as ONE Pallas call, `kda_state_step`: grid (phases of
    slots,), the state left in HBM (`pl.ANY`), read once and written in
    place by the kernel's own DMAs through a ring of three phase buffers;
    a phase's columns, its rows of v and of o come and go through
    BlockSpecs."""
    n, H, dk, dv = S.shape
    f32 = jnp.float32
    ks = _step_slots(n, H, dk, dv)
    live = jnp.ones((n,), jnp.int32) if live is None \
        else live.astype(jnp.int32)
    kf = k.astype(f32)
    # (S, 4, H, d_k) -> (S, d_k, 4 H): a head's column is one lane
    cols = jnp.stack([jnp.exp(g.astype(f32)), kf,
                      beta.astype(f32)[..., None] * kf, q.astype(f32)],
                     axis=1).reshape(n, 4 * H, dk).swapaxes(1, 2)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    rows = pl.BlockSpec((ks, H, dv), lambda i, live_ref: (i, 0, 0))
    S_new, o = pl.pallas_call(
        functools.partial(_step_kernel, k=ks, H=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // ks,),
            in_specs=[in_hbm,
                      pl.BlockSpec((ks, dk, 4 * H),
                                   lambda i, live_ref: (i, 0, 0)),
                      rows],
            out_specs=[in_hbm, rows],
            scratch_shapes=[pltpu.VMEM((_RING, ks, H, dk, dv), f32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct(S.shape, f32),
                   jax.ShapeDtypeStruct((n, H, dv), f32)],
        input_output_aliases={1: 0},
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",)),
        name="kda_state_step",
        interpret=interpret,
    )(live, S, cols, v.astype(f32))
    return o, S_new


def kda_step_kernel_decline(S, *, interpret: bool = False):
    """Why `kda_step_kernel` cannot take this call (None = it can)."""
    if not interpret and jax.default_backend() != "tpu":
        return f"the {jax.default_backend()} backend is no TPU"
    if S.ndim != 4 or S.dtype != jnp.float32:
        return (f"state {S.dtype}{list(S.shape)} is not float32 "
                "(S, H, d_k, d_v)")
    n, H, dk, dv = S.shape
    lane = 8 if interpret else 128
    if dk % 8 != 0 or dv % lane != 0:
        return (f"a head's state of {dk} x {dv} is no whole tiles of "
                f"8 x {lane}")
    from distributed_pytorch_tpu.parallel import context
    mesh = context.get_mesh()
    if mesh is not None and any(s > 1 for s in mesh.devices.shape):
        return ("a live multi-device mesh (GSPMD cannot partition a "
                "pallas_call)")
    return _budget_decline(_step_vmem_bytes(_step_slots(n, H, dk, dv), H,
                                            dk, dv))


def kda_step_kernel_usable(S) -> bool:
    return kda_step_kernel_decline(S) is None


def kda_step(S, q, k, v, g, beta, live=None):
    """One token. S (S, H, d_k, d_v) float32; q, k (S, H, d_k), q scaled
    and both normalised already; v (S, H, d_v); g (S, H, d_k) the log decay
    (<= 0); beta (S, H). Returns (o (S, H, d_v) float32, S'). Rows where
    `live` (S,) is False keep their state."""
    why = kda_step_kernel_decline(S)
    if why is None:
        paths.note("kda_step", "kda_state_step",
                   "state in place, %d slots a phase, a ring of %d"
                   % (_step_slots(*S.shape), _RING))
        return kda_step_kernel(S, q, k, v, g, beta, live)
    paths.note("kda_step", "xla", f"kda_step_kernel_decline: {why}")
    return kda_step_xla(S, q, k, v, g, beta, live)


def _unit_lower_inverse(A):
    """(I + A)^-1 for strictly lower triangular A (..., C, C), by forward
    substitution: row i of the inverse is e_i - sum_{j<i} A_ij row_j."""
    C = A.shape[-1]
    eye = jnp.eye(C, dtype=A.dtype)
    T = jnp.broadcast_to(eye, A.shape)
    for i in range(1, C):
        row = eye[i] - jnp.einsum("...j,...jc->...c", A[..., i, :i],
                                  T[..., :i, :], precision=_HI)
        T = T.at[..., i, :].set(row)
    return T


def kda_scan(q, k, v, g, beta, S0=None):
    """The literal recurrence a row at a time (`lax.scan`): what the tests
    and the kernel bench hold both forms to; no program runs it. Shapes as
    `kda_chunk`."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    S0 = jnp.zeros((H, dk, dv), f32) if S0 is None else S0.astype(f32)

    def step(S, x):
        o, S = kda_step_xla(S[None], *(t[None] for t in x))
        return S[0], o[0]

    S, o = jax.lax.scan(step, S0, tuple(
        t.astype(f32) for t in (q, k, v, g, beta)))
    return o, S


def kda_chunk(q, k, v, g, beta, S0=None):
    """A prefill chunk of ONE sequence. q, k (T, H, d_k), q scaled and
    both normalised already; v (T, H, d_v); g (T, H, d_k) the log decay, 0
    on pad rows; beta (T, H), 0 on pad rows; S0 (H, d_k, d_v) float32 the
    state at the chunk's start, or None for zeros. Returns (o (T, H, d_v)
    float32, S_T (H, d_k, d_v)). T is padded up to whole sub-chunks of
    `SUB_CHUNK` rows here, with g = 0 and beta = 0. Fused XLA whatever the
    shapes and the backend: a gate comes with the kernel that needs one."""
    paths.note("kda_chunk", "xla_wy",
               f"forward substitution in sub-chunks of {SUB_CHUNK} rows")
    T, H, dk = q.shape
    C = min(SUB_CHUNK, T)
    f32 = jnp.float32
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
    pad = (-T) % C
    if pad:
        q, k, v, g, beta = (jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1))
                            for t in (q, k, v, g, beta))
    n = (T + pad) // C

    def sub(t):                          # (T, H, .) -> (n, H, C, .)
        return t.reshape(n, C, H, -1).swapaxes(1, 2)

    q, k, v, g = sub(q), sub(k), sub(v), sub(g)
    beta = beta.reshape(n, C, H).swapaxes(1, 2)             # (n, H, C)
    gam = jnp.cumsum(g, axis=2)                             # (n, H, C, dk)
    mid = gam[:, :, C // 2:C // 2 + 1]
    dec, inv = jnp.exp(gam - mid), jnp.exp(mid - gam)       # about row m
    k_inv = k * inv
    lower = jnp.tril(jnp.ones((C, C), bool), -1)
    A = jnp.where(lower, jnp.einsum("nhic,nhjc->nhij", k * dec, k_inv,
                                    precision=_HI), 0.0) * beta[:, :, None]
    P = jnp.where(lower | jnp.eye(C, dtype=bool),
                  jnp.einsum("nhic,nhjc->nhij", q * dec, k_inv,
                             precision=_HI), 0.0) * beta[:, :, None]
    Tm = _unit_lower_inverse(A)                             # (n, H, C, C)
    from_start = jnp.exp(gam)                               # <= 1
    k_start, q_start = k * from_start, q * from_start
    total = from_start[:, :, -1]                            # (n, H, dk)
    k_end = k * jnp.exp(gam[:, :, -1:] - gam) * beta[..., None]
    return _wy_rows(S0, k_start, q_start, v, Tm, P, k_end, total, T)


def _wy_rows(S0, k_start, q_start, v, Tm, P, k_end, total, T: int):
    """The last three lines of the chunked form (the header's), sub-chunk
    after sub-chunk: operands (n, H, C, .), `Tm` = (I + A)^-1 and `P`
    (n, H, C, C), `total` (n, H, d_k) what a sub-chunk's decays leave of a
    state row, S0 (H, d_k, d_v) or None for zeros -> (o (T, H, d_v), S)."""
    n, H, C, dk = k_start.shape
    dv = v.shape[-1]
    S0 = jnp.zeros((H, dk, dv), jnp.float32) if S0 is None \
        else S0.astype(jnp.float32)

    def step(S, x):
        k_s, q_s, v_n, T_n, P_n, k_e, tot = x
        W = jnp.einsum("hij,hjv->hiv", T_n, v_n - jnp.einsum(
            "hic,hcv->hiv", k_s, S, precision=_HI), precision=_HI)
        o = jnp.einsum("hic,hcv->hiv", q_s, S, precision=_HI) \
            + jnp.einsum("hij,hjv->hiv", P_n, W, precision=_HI)
        S = tot[..., None] * S + jnp.einsum("hic,hiv->hcv", k_e, W,
                                            precision=_HI)
        return S, o

    S, o = jax.lax.scan(step, S0, (k_start, q_start, v, Tm, P, k_end, total))
    return o.swapaxes(1, 2).reshape(n * C, H, dv)[:T], S


def gdn_scan(q, k, v, g, beta, S0=None):
    """`kda_scan` for a decay a HEAD, g (T, H): the literal recurrence the
    tests hold `gdn_chunk` and the step to; no program runs it."""
    return kda_scan(q, k, v, jnp.broadcast_to(
        g[..., None], (*g.shape, q.shape[-1])), beta, S0)


def gdn_chunk(q, k, v, g, beta, S0=None):
    """`kda_chunk`'s chunk of ONE sequence for the gated delta rule with ONE
    log decay a head (Gated DeltaNet): g (T, H), ANY g <= 0; the rest as
    there (q, k (T, H, d_k) a row a VALUE head, key heads repeated
    already). With c the running sum of g inside a sub-chunk the header's
    A, P and the state's update need only

        D_ij = exp(c_i - c_j), i >= j        (0 above the diagonal)
        exp(c_i),  exp(c_C - c_i)

    every exponent <= 0: a scalar a head and row comes out of the sum over
    the key channels, so nothing is factored about a middle row and
    nothing is inverted. A decay of -80 a token underflows to an exact 0
    where the literal recurrence's does. Fused XLA, float32, HIGHEST, in
    sub-chunks of `SUB_CHUNK` rows as `kda_chunk`'s: a form that exploits
    the scalar decay for SPEED (whole (C, C) products on the MXU at a wider
    sub-chunk) is a later `perf_opt`'s to bring, from `gdn_chunk_roofline`."""
    paths.note("gdn_chunk", "xla_wy",
               f"a decay a head, exp(c_i - c_j) for i >= j alone, "
               f"sub-chunks of {SUB_CHUNK} rows")
    T, H, dk = q.shape
    C = min(SUB_CHUNK, T)
    f32 = jnp.float32
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
    pad = (-T) % C
    if pad:
        q, k, v, g, beta = (jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1))
                            for t in (q, k, v, g, beta))
    n = (T + pad) // C

    def sub(t):                          # (T, H, .) -> (n, H, C, .)
        return t.reshape(n, C, H, -1).swapaxes(1, 2)

    q, k, v = sub(q), sub(k), sub(v)
    g, beta = (t.reshape(n, C, H).swapaxes(1, 2) for t in (g, beta))
    c = jnp.cumsum(g, axis=2)                               # (n, H, C)
    lower = jnp.tril(jnp.ones((C, C), bool), -1)
    upto = lower | jnp.eye(C, dtype=bool)
    # masked BEFORE the exponential: above the diagonal c_i - c_j > 0
    D = jnp.exp(jnp.where(upto, c[..., :, None] - c[..., None, :], -jnp.inf))
    Db = D * beta[:, :, None]
    A = jnp.where(lower, jnp.einsum("nhic,nhjc->nhij", k, k,
                                    precision=_HI), 0.0) * Db
    P = jnp.einsum("nhic,nhjc->nhij", q, k, precision=_HI) * Db
    Tm = _unit_lower_inverse(A)
    from_start = jnp.exp(c)[..., None]                      # <= 1
    total = jnp.broadcast_to(from_start[:, :, -1], (n, H, dk))
    k_end = k * (jnp.exp(c[:, :, -1:] - c) * beta)[..., None]
    return _wy_rows(S0, k * from_start, q * from_start, v, Tm, P, k_end,
                    total, T)
