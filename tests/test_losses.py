"""Fused (chunked) cross-entropy vs the unchunked oracle.

The fused path is the round-4 MFU fix (never materializes (B, T, V) fp32
logits — ops/losses.py); these tests pin its numerics and gradients to the
full-logits oracle, which itself mirrors reference single-gpu/model.py:
687-692 (ignore_index=-1 mean CE)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu.config import LLMConfig
from distributed_pytorch_tpu.models import LLM
from distributed_pytorch_tpu.ops.losses import (_chunk_for,
                                                fused_cross_entropy,
                                                unchunked_cross_entropy)


def _data(B=2, T=32, C=16, V=64, seed=0):
    kx, ke, kt = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (B, T, C), jnp.float32)
    emb = jax.random.normal(ke, (V, C), jnp.float32) * 0.1
    tgt = jax.random.randint(kt, (B, T), 0, V)
    return x, emb, tgt


@pytest.mark.parametrize("chunk, V", [(4, 64), (8, 64), (16, 64),
                                      # off the lane grid, and a chunk of 2
                                      (2, 64), (2, 96), (2, 100)])
def test_fused_matches_unchunked(chunk, V):
    x, emb, tgt = _data(V=V)
    ref = unchunked_cross_entropy(x, emb, tgt)
    got = fused_cross_entropy(x, emb, tgt, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)


def test_fused_gradients_match():
    x, emb, tgt = _data()

    g_ref = jax.grad(lambda a, e: unchunked_cross_entropy(a, e, tgt),
                     argnums=(0, 1))(x, emb)
    g_fused = jax.grad(lambda a, e: fused_cross_entropy(a, e, tgt, chunk=8),
                       argnums=(0, 1))(x, emb)
    for r, f in zip(g_ref, g_fused):
        np.testing.assert_allclose(np.asarray(f), np.asarray(r),
                                   rtol=1e-5, atol=1e-6)


def test_fused_ignore_index():
    x, emb, tgt = _data()
    tgt = tgt.at[:, 16:].set(-1)
    ref = unchunked_cross_entropy(x, emb, tgt)
    got = fused_cross_entropy(x, emb, tgt, chunk=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)
    # all-masked: finite zero, not NaN (denominator clamps at 1)
    all_masked = jnp.full_like(tgt, -1)
    got0 = fused_cross_entropy(x, emb, all_masked, chunk=8)
    assert float(got0) == 0.0


def test_chunk_autoselect():
    # tiny vocab / short T: never chunk (scan overhead would hurt)
    assert _chunk_for(32, 96) == 0
    assert _chunk_for(128, 96) == 0
    # GPT-scale: chunk divides T and is <= the target
    c = _chunk_for(1024, 50304)
    assert c > 0 and 1024 % c == 0 and c <= 128
    # awkward T (prime / tiny-divisor-only): degenerate chunks would scan
    # near-per-token — must fall back to unchunked, not chunk=1/2
    assert _chunk_for(1021, 50304) == 0
    assert _chunk_for(2 * 509, 50304) == 0


def test_model_loss_impl_parity():
    """End-to-end: LLM with loss_impl='fused' (forced chunking) matches
    loss_impl='unchunked' bit-for-bit in fp32, gradients included."""
    kw = dict(vocab_size=96, block_size=32, n_embd=32, n_head=4,
              n_kv_heads=2, n_layer=2, up_dim=48, pos_emb="rope",
              attn="gqa", non_linearity="swiglu")
    cfg_f = LLMConfig(**kw, loss_impl="fused", loss_chunk=4)
    cfg_u = LLMConfig(**kw, loss_impl="unchunked")
    idx = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 96)
    tgt = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 96)
    model_f, model_u = LLM(cfg_f), LLM(cfg_u)
    variables = model_u.init(jax.random.PRNGKey(0), idx, tgt)

    _, loss_u, _ = model_u.apply(variables, idx, tgt)
    _, loss_f, _ = model_f.apply(variables, idx, tgt)
    np.testing.assert_allclose(np.asarray(loss_f), np.asarray(loss_u),
                               rtol=1e-6)

    def lf(m):
        return lambda p: m.apply({"params": p}, idx, tgt)[1]

    g_u = jax.grad(lf(model_u))(variables["params"])
    g_f = jax.grad(lf(model_f))(variables["params"])
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=1e-5, atol=1e-6),
        g_f, g_u)


# ---------------------------------------------------------------------------
# the rule under a gradient: dx and dW taken inside the forward chunk scan
# ---------------------------------------------------------------------------

def _checkpointed_scan_ce(x, emb, tgt, chunk, ignore_index=-1):
    """What `fused_cross_entropy` was before it had a rule of its own: the
    chunk scan under `jax.checkpoint`, left to autodiff (every logits block
    built again in backward). Kept here as the yardstick of the rule's
    rounding."""
    B, T, C = x.shape
    n = T // chunk
    xs = jnp.moveaxis(x.reshape(B, n, chunk, C), 1, 0)
    ts = jnp.moveaxis(tgt.reshape(B, n, chunk), 1, 0)

    @jax.checkpoint
    def block(x_c, t_c):
        logits = jax.lax.dot_general(
            x_c, emb, (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        mask = t_c != ignore_index
        lse = jax.nn.logsumexp(logits, axis=-1)
        tg = jnp.take_along_axis(
            logits, jnp.where(mask, t_c, 0)[..., None], axis=-1)[..., 0]
        return jnp.where(mask, lse - tg, 0.0).sum(), mask.sum()

    _, (sums, counts) = jax.lax.scan(lambda c, xt: (c, block(*xt)), None,
                                     (xs, ts))
    return sums.sum() / jnp.maximum(counts.sum(), 1)


def _float32_oracle_grads(x, emb, tgt):
    def exact(a, e):
        return jnp.einsum("btc,vc->btv", a, e, precision="highest")

    return jax.grad(
        lambda a, e: unchunked_cross_entropy(a, e, tgt, logits_fn=exact),
        argnums=(0, 1))(x.astype(jnp.float32), emb.astype(jnp.float32))


def _rel_rms(got, want):
    got = np.asarray(got, np.float32)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_rule_rounds_no_worse_than_the_checkpointed_scan_in_bf16(seed):
    """bf16 operands at a vocabulary the auto chunking engages for: the
    rule's dx and dW against the float32 oracle, no further off than the
    autodiff of the scan it replaced (same matmuls, same prologue, a dW
    accumulator of the same dtype)."""
    x, emb, tgt = _data(B=2, T=256, C=32, V=8192, seed=seed)
    x, emb = x.astype(jnp.bfloat16), emb.astype(jnp.bfloat16)
    tgt = tgt.at[1, 200:].set(-1)
    want = [np.asarray(g) for g in _float32_oracle_grads(x, emb, tgt)]
    rule = jax.grad(lambda a, e: fused_cross_entropy(a, e, tgt),
                    argnums=(0, 1))(x, emb)          # chunk 0 -> 128
    was = jax.grad(lambda a, e: _checkpointed_scan_ce(a, e, tgt, 128),
                   argnums=(0, 1))(x, emb)
    for name, r, w, ref in zip(("dx", "dW"), rule, was, want):
        assert r.dtype == jnp.bfloat16
        err, err_was = _rel_rms(r, ref), _rel_rms(w, ref)
        assert err <= 1.05 * err_was, (name, err, err_was)
        assert err < 0.02, (name, err)


@pytest.mark.parametrize("masked", ["part_of_a_chunk", "a_whole_chunk",
                                    "everything"])
def test_rule_gradients_under_ignore_index(masked):
    x, emb, tgt = _data()
    tgt = {"part_of_a_chunk": tgt.at[:, 11:16].set(-1),
           "a_whole_chunk": tgt.at[:, 8:16].set(-1),
           "everything": jnp.full_like(tgt, -1)}[masked]
    ref, g_ref = jax.value_and_grad(
        lambda a, e: unchunked_cross_entropy(a, e, tgt), argnums=(0, 1))(
        x, emb)
    got, g_got = jax.value_and_grad(
        lambda a, e: fused_cross_entropy(a, e, tgt, chunk=8),
        argnums=(0, 1))(x, emb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)
    for r, g in zip(g_ref, g_got):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-5, atol=1e-6)
    if masked == "everything":
        assert float(got) == 0.0
        assert all(not np.asarray(g).any() for g in g_got)
    else:   # a masked row takes no gradient
        np.testing.assert_array_equal(np.asarray(g_got[0][:, 11:16]), 0.0)


def test_rule_scales_with_the_cotangent():
    x, emb, tgt = _data()

    def loss(a, e):
        return fused_cross_entropy(a, e, tgt, chunk=8)

    g1 = jax.grad(loss, argnums=(0, 1))(x, emb)
    g3 = jax.grad(lambda a, e: 3.0 * loss(a, e) + 1.0, argnums=(0, 1))(
        x, emb)
    for a, b in zip(g1, g3):
        np.testing.assert_allclose(np.asarray(b), 3.0 * np.asarray(a),
                                   rtol=1e-6)


def test_rule_inside_a_scan_over_microbatches():
    """As `train/step.py::micro_step`: value_and_grad inside a `lax.scan`
    over microbatches, gradients summed in the carry."""
    x, emb, tgt = _data(B=4)
    xm, tm = x.reshape(2, 2, *x.shape[1:]), tgt.reshape(2, 2, -1)

    def accumulate(loss_fn):
        def micro(acc, xt):
            l, g = jax.value_and_grad(
                lambda a, e: loss_fn(a, e, xt[1]), argnums=(0, 1))(
                xt[0], emb)
            return acc + g[1], (l, g[0])
        return jax.jit(lambda: jax.lax.scan(
            micro, jnp.zeros_like(emb), (xm, tm)))()

    dW, (ls, dxs) = accumulate(
        lambda a, e, t: fused_cross_entropy(a, e, t, chunk=8))
    dW_r, (ls_r, dxs_r) = accumulate(unchunked_cross_entropy)
    for got, ref in ((dW, dW_r), (ls, ls_r), (dxs, dxs_r)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)


def test_rule_at_the_real_padded_vocabulary():
    """GPT-2's 50,304 rows (393 x 128, no multiple of a power of two past
    128), tiny N and C: value and dW of the rule against the full logits."""
    x, emb, tgt = _data(B=2, T=32, C=128, V=50304)
    ref, g_ref = jax.value_and_grad(
        lambda e: unchunked_cross_entropy(x, e, tgt))(emb)
    got, g_got = jax.value_and_grad(
        lambda e: fused_cross_entropy(x, e, tgt, chunk=8))(emb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref),
                               rtol=2e-5, atol=2e-6)


def test_rule_under_a_data_axis_shard_map():
    """Each of 8 devices runs the rule over its own rows inside a
    `shard_map` over 'data' (the embedding replicated, its cotangent summed
    by the map's transpose): value and both gradients of the unsharded
    call."""
    from jax.sharding import PartitionSpec as P

    from distributed_pytorch_tpu import compat
    from distributed_pytorch_tpu.parallel.mesh import mesh_for

    x, emb, tgt = _data(B=8, T=32)      # nothing masked: equal counts

    def loss(a, e, t):
        return fused_cross_entropy(a, e, t, chunk=8)

    sharded = compat.shard_map(
        lambda a, e, t: jax.lax.pmean(loss(a, e, t), "data"),
        mesh=mesh_for("dp"), in_specs=(P("data"), P(), P("data")),
        out_specs=P())
    ref, g_ref = jax.value_and_grad(loss, argnums=(0, 1))(x, emb, tgt)
    got, g_got = jax.value_and_grad(sharded, argnums=(0, 1))(x, emb, tgt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)
    for r, g in zip(g_ref, g_got):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-5, atol=2e-6)


def test_the_kernels_name_is_refused_and_fused_is_named():
    """A stored config that still says 'pallas' fails at construction: it
    never runs 'fused' under the kernel's name."""
    with pytest.raises(ValueError, match="PR 48.*'fused'"):
        LLMConfig(loss_impl="pallas")


_V = 8192


def _count_eqns(jaxpr, counts=None):
    """{'scan': n, 'head_dot': n} over a jaxpr and everything it calls: a
    `dot_general` counts if the vocabulary is in its shapes."""
    counts = counts if counts is not None else {"scan": 0, "head_dot": 0}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            counts["scan"] += 1
        elif eqn.primitive.name == "dot_general" and any(
                _V in v.aval.shape for v in (*eqn.invars, *eqn.outvars)):
            counts["head_dot"] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _count_eqns(sub, counts)
    return counts


@pytest.mark.parametrize("what, want", [
    ("gradient", {"scan": 1, "head_dot": 3}),
    ("primal", {"scan": 1, "head_dot": 1})])
def test_rule_structure(what, want):
    """Under a gradient ONE scan holds the head matmul, dx and dW (the
    checkpointed scan held two loops and four); undifferentiated, one
    matmul a chunk and nothing else."""
    x, emb, tgt = _data(B=2, T=256, C=32, V=_V)

    def loss(a, e):
        return fused_cross_entropy(a, e, tgt)

    fn = jax.grad(loss, argnums=(0, 1)) if what == "gradient" else loss
    assert _count_eqns(jax.make_jaxpr(fn)(x, emb).jaxpr) == want
    if what == "gradient":
        was = jax.make_jaxpr(jax.grad(
            lambda a, e: _checkpointed_scan_ce(a, e, tgt, 128),
            argnums=(0, 1)))(x, emb)
        assert _count_eqns(was.jaxpr) == {"scan": 2, "head_dot": 4}


@pytest.mark.parametrize("differentiated", [True, False])
def test_the_census_names_the_rule_that_ran(differentiated):
    from distributed_pytorch_tpu.obs import paths
    cfg = LLMConfig(vocab_size=96, block_size=32, n_embd=32, n_head=4,
                    n_kv_heads=4, n_layer=1, up_dim=48, loss_impl="fused",
                    loss_chunk=4)
    idx = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 96)
    model = LLM(cfg)
    variables = model.init(jax.random.PRNGKey(0), idx, idx)

    def loss(p):
        return model.apply({"params": p}, idx, idx)[1]

    paths.reset()
    jax.make_jaxpr(jax.grad(loss) if differentiated else loss)(
        variables["params"])
    want = ("fused, gradients in the forward scan" if differentiated
            else "fused, plain scan")
    assert paths.choices()["loss"] == f"{want} (4 chunks of 4 tokens)"
    paths.reset()


def _sp_fused_loss_against_the_oracle(chunk):
    from distributed_pytorch_tpu.ops.losses import sp_fused_cross_entropy
    from distributed_pytorch_tpu.parallel import context
    from distributed_pytorch_tpu.parallel.mesh import mesh_for

    x, emb, tgt = _data(B=8, T=32, C=16, V=64, seed=3)
    tgt = tgt.at[:, 28:].set(-1)
    ref, g_ref = jax.value_and_grad(
        lambda a, e: unchunked_cross_entropy(a, e, tgt), argnums=(0, 1))(
        x, emb)
    mesh = mesh_for("sp", sp_size=2)
    with context.use_mesh(mesh):
        got, g_got = jax.value_and_grad(
            lambda a, e: sp_fused_cross_entropy(a, e, tgt, chunk=chunk),
            argnums=(0, 1))(x, emb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)
    for r, g in zip(g_ref, g_got):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("chunk", [0, 8])
def test_sp_fused_loss_matches_oracle(chunk):
    """Sequence-parallel chunked CE (round-5: replaces the unchunked
    fallback under a live 'seq' axis): value and grads must match the
    full-logits oracle on a data=4 x seq=2 mesh, with and without an
    explicit chunk size, including masked targets."""
    _sp_fused_loss_against_the_oracle(chunk)


def test_sp_fused_loss_under_a_checked_shard_map(monkeypatch):
    """The same under `check_vma=True` (the repo's shard_map leaves it off):
    the dW carry, the pulled-back scalar and the embedding are typed to
    vary as the shard's rows do, so the rule's types close."""
    import functools

    from distributed_pytorch_tpu import compat

    monkeypatch.setattr(compat, "shard_map",
                        functools.partial(compat.shard_map, check=True))
    _sp_fused_loss_against_the_oracle(8)


def test_sp_train_step_uses_chunked_loss():
    """End-to-end: an sp-recipe train step at fused loss_impl must agree
    with the single-device oracle (this now routes through
    sp_fused_cross_entropy at trace time)."""
    from distributed_pytorch_tpu.config import TrainConfig
    from distributed_pytorch_tpu.parallel import context
    from distributed_pytorch_tpu.parallel.mesh import mesh_for
    from distributed_pytorch_tpu.train.state import create_train_state
    from distributed_pytorch_tpu.train.step import make_train_step

    mc = LLMConfig(vocab_size=128, block_size=32, n_embd=32, n_head=4,
                   n_kv_heads=4, n_layer=2, up_dim=64, loss_impl="fused",
                   loss_chunk=8)
    x = jax.random.randint(jax.random.PRNGKey(1), (1, 8, 32), 0, 128)
    y = jax.random.randint(jax.random.PRNGKey(2), (1, 8, 32), 0, 128)

    tc1 = TrainConfig(total_batch_size=8 * 32, batch_size=8, max_iters=2,
                      parallelism="single")
    model, tx, state, _ = create_train_state(mc, tc1, None)
    step = make_train_step(model, tx, mc, tc1, None, None)
    _, m_ref = step(state, x, y)

    tc2 = TrainConfig(total_batch_size=8 * 32, batch_size=8, max_iters=2,
                      parallelism="sp", sp_size=2)
    mesh = mesh_for("sp", sp_size=2)
    with context.use_mesh(mesh):
        model2, tx2, state2, sh2 = create_train_state(mc, tc2, mesh)
        step2 = make_train_step(model2, tx2, mc, tc2, mesh, sh2)
        _, m_sp = step2(state2, x, y)
    np.testing.assert_allclose(float(m_sp["loss"]), float(m_ref["loss"]),
                               rtol=2e-5)
