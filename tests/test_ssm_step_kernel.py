"""The one-token recurrence's Pallas kernel (`ops/ssm_scan.py`
`ssm_state_step`) against the jax.numpy line, in interpret mode, at the
three published head / group / state ratios in small: 128 heads x 64 over
one group (granite), 64 x 64 over 8 groups (nemotron), 32 x 128 over 2
groups at twice the state (falcon). What the chip's compiler makes of the
real sizes is `tests/test_aot_tpu_compile.py`'s."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu.obs import paths
from distributed_pytorch_tpu.ops import ssm_scan

# heads, head size, groups, state
RATIOS = pytest.mark.parametrize(
    "H, P, G, N", [(16, 8, 1, 16), (8, 8, 8, 16), (4, 16, 2, 32)],
    ids=["16heads_1group", "8heads_8groups", "4heads_of_16_2groups"])

kernel = functools.partial(ssm_scan.ssm_step_kernel, interpret=True)


def _operands(S, H, P, G, N, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(k[0], (S, N, H * P), jnp.float32),
            jax.random.normal(k[1], (S, H, P), jnp.bfloat16),
            jax.nn.softplus(jax.random.normal(k[2], (S, H))),
            -jnp.exp(jax.random.normal(k[3], (H,))),
            jax.random.normal(k[4], (S, G, N), jnp.bfloat16),
            jax.random.normal(k[5], (S, G, N), jnp.bfloat16),
            jax.random.normal(k[6], (H,)))


@pytest.fixture
def phases_of_3(monkeypatch):
    """Three slots a phase at most (every ratio's slot is 4 x 16 x 128 or
    4 x 32 x 64 bytes): the call has several phases, so a buffer is read
    into while the other is worked on."""
    monkeypatch.setattr(ssm_scan, "_PHASE_BYTES", 3 * 4 * 32 * 128)
    jax.clear_caches()
    yield
    jax.clear_caches()


@RATIOS
def test_the_kernel_is_the_xla_line(H, P, G, N, phases_of_3):
    """y and h' within 1e-5 of the jax.numpy line (float32 both, the sum
    over the state axis in another order), and a dead slot's state
    untouched bit for bit."""
    S = 9
    h, *ops = _operands(S, H, P, G, N)
    live = jnp.arange(S) % 4 != 1
    before = np.array(h)
    y0, h0 = ssm_scan.ssm_step_xla(h, *ops, live)
    y1, h1 = kernel(h, *ops, live)
    scale = float(jnp.abs(y0).max())
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-5 * scale)
    np.testing.assert_allclose(h1, h0, rtol=1e-5, atol=1e-6)
    dead = ~np.asarray(live)
    assert dead.sum() == 2
    assert np.array_equal(np.asarray(h1)[dead], before[dead])
    assert not np.array_equal(np.asarray(h1)[~dead], before[~dead])
    # no mask at all: every slot advances
    y2, h2 = kernel(h, *ops)
    np.testing.assert_allclose(
        h2, ssm_scan.ssm_step_xla(h, *ops)[1], rtol=1e-5, atol=1e-6)


@RATIOS
def test_a_slot_is_blind_to_its_neighbours(H, P, G, N, phases_of_3):
    """Slot 40 of 64 reads the same BITS among 63 random neighbours, among
    zeros, and alone in a call of one slot a phase at another place: its
    y and h' depend on its own operands and the call's shapes (the repeat
    share of the benchmark's `correct` rests on it)."""
    S, at = 64, 40
    full = _operands(S, H, P, G, N, seed=3)
    live = jnp.ones((S,), bool)
    y, h = kernel(*full, live)

    def only(t):            # slot `at` kept, every other slot zeroed
        return t if t.ndim == 1 else jnp.zeros_like(t).at[at].set(t[at])
    y0, h0 = kernel(*[only(t) for t in full], live)
    assert np.array_equal(np.asarray(y)[at], np.asarray(y0)[at])
    assert np.array_equal(np.asarray(h)[at], np.asarray(h0)[at])
    moved = [t if t.ndim == 1 else jnp.roll(t, 5, axis=0) for t in full]
    y5, h5 = kernel(*moved, live)
    assert np.array_equal(np.asarray(y)[at], np.asarray(y5)[at + 5])
    assert np.array_equal(np.asarray(h)[at], np.asarray(h5)[at + 5])


def _named(S, H, P, G, N):
    h, x, _, _, B, *_ = _operands(S, H, P, G, N)
    return h, x, B


@pytest.mark.parametrize("shape, interpret, says", [
    ((4, 4, 8, 1, 12), True, "12 rows is no multiple of 8"),
    ((4, 4, 3, 4, 16), True, "12 lanes over 4 groups"),
    ((4, 4, 12, 1, 16), "as on the chip", "no whole tiles of 128"),
    ((4, 16, 8, 1, 16), False, "backend is no TPU"),
], ids=["state_rows", "group_lanes", "lane_tiles_of_the_chip", "off_the_chip"])
def test_the_gate_names_its_reason_and_the_xla_line_takes_the_call(
        shape, interpret, says, monkeypatch):
    h, x, B = _named(*shape)
    if interpret == "as on the chip":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        interpret = False
    why = ssm_scan.ssm_step_kernel_decline(h, x, B, interpret=interpret)
    assert why is not None and says in why, why
    ops = _operands(*shape)
    paths.reset()
    y, h1 = ssm_scan.ssm_step(*ops)
    y0, h0 = ssm_scan.ssm_step_xla(*ops)
    assert np.array_equal(y, y0) and np.array_equal(h1, h0)
    assert paths.choices()["ssm_step"].startswith(
        "xla (ssm_step_kernel_decline: ")


def test_a_state_that_is_not_float32_state_major_is_declined():
    h, x, B = _named(4, 16, 8, 1, 16)
    assert "not float32" in ssm_scan.ssm_step_kernel_decline(
        h.astype(jnp.bfloat16), x, B, interpret=True)
    assert "not float32" in ssm_scan.ssm_step_kernel_decline(
        h.reshape(4, 16, 16, 8), x, B, interpret=True)
    assert ssm_scan.ssm_step_kernel_decline(h, x, B, interpret=True) is None


def test_the_path_note_says_which_way_a_call_went(monkeypatch):
    """Where the gate lets the call through, the note names the kernel and
    its phase; the kernel's own name is not the scope's."""
    ops = _operands(6, 16, 8, 1, 16)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ssm_scan, "ssm_step_kernel", kernel)
    paths.reset()
    y, h = ssm_scan.ssm_step(*ops)
    assert paths.choices()["ssm_step"] == \
        "ssm_state_step (state in place, 6 slots a phase)"
    np.testing.assert_allclose(h, ssm_scan.ssm_step_xla(*ops)[1],
                               rtol=1e-5, atol=1e-6)


def test_a_phase_is_whole_slots_that_divide_the_call():
    MB = 1 << 20
    assert ssm_scan._PHASE_BYTES == 16 * MB
    # 4.19 MB a slot (granite, falcon): 4 a phase; 2.1 MB (nemotron): 8
    # (slots a phase, lanes a pass, rows a block of x / dt / B / C / y)
    assert ssm_scan._step_shape(64, 128, 8192, 1, 128) == (4, 2048, 8)
    assert ssm_scan._step_shape(64, 256, 4096, 2, 128) == (4, 2048, 8)
    assert ssm_scan._step_shape(64, 128, 4096, 8, 128) == (8, 512, 8)
    # a phase divides the slots; one slot at least, whatever its size; the
    # rows come 8 at a time where a phase divides 8, else all at once
    assert ssm_scan._step_shape(6, 128, 8192, 1, 128) == (3, 2048, 6)
    assert ssm_scan._step_shape(5, 128, 8192, 1, 128) == (1, 2048, 5)
    assert ssm_scan._step_shape(16, 1024, 8192, 1, 128) == (1, 2048, 8)
    assert ssm_scan._step_shape(128, 128, 1024, 1, 128) == (32, 1024, 32)
    # and the gate holds two phases and their rows to the VMEM budget
    h = jax.ShapeDtypeStruct((2, 2048, 8192), jnp.float32)
    x = jax.ShapeDtypeStruct((2, 64, 128), jnp.bfloat16)
    B = jax.ShapeDtypeStruct((2, 1, 2048), jnp.bfloat16)
    assert "VMEM" in ssm_scan.ssm_step_kernel_decline(h, x, B,
                                                      interpret=True)
