"""MFU / FLOPs accounting tests (train/metrics.py): the honesty of the
headline benchmark number rests on these formulas — MoE counts only active
experts, remat policies add exactly their recompute, and the per-token
matmul census matches a hand count."""

from distributed_pytorch_tpu.config import LLMConfig, flagship_gpt124m
from distributed_pytorch_tpu.train import metrics as M


def test_dense_matmul_census_hand_count():
    cfg = LLMConfig(vocab_size=100, block_size=32, n_embd=8, n_head=2,
                    n_kv_heads=2, n_layer=1, up_dim=16,
                    non_linearity="relu", pos_emb="learn", attn="mha")
    C, up, V = 8, 16, 100
    attn = C * (C + 2 * 2 * 4) + C * C      # fused qkv + out proj
    ffn = C * up + up * C                   # relu: single up projection
    expected = attn + ffn + V * C           # + tied lm head
    assert M.matmul_params_per_token(cfg) == expected


def test_swiglu_doubles_up_projection():
    base = dict(vocab_size=100, block_size=32, n_embd=8, n_head=2,
                n_kv_heads=2, n_layer=1, up_dim=16, pos_emb="learn",
                attn="mha")
    relu = M.matmul_params_per_token(LLMConfig(**base, non_linearity="relu"))
    swiglu = M.matmul_params_per_token(
        LLMConfig(**base, non_linearity="swiglu"))
    assert swiglu - relu == 8 * 16          # one extra (C, up) gate matrix


def test_moe_counts_only_active_experts():
    base = dict(vocab_size=100, block_size=32, n_embd=8, n_head=2,
                n_kv_heads=2, n_layer=1, up_dim=16, non_linearity="relu",
                pos_emb="learn", attn="mha")
    dense = M.matmul_params_per_token(LLMConfig(**base))
    moe = M.matmul_params_per_token(LLMConfig(
        **base, moe=True, n_exp=8, n_shared=1, n_act=3))
    one_mlp = 8 * 16 + 16 * 8
    router = 8 * 7                           # C x n_routed
    # 1 shared + 2 active routed = 3 MLPs vs the dense model's 1
    assert moe - dense == 2 * one_mlp + router


def test_remat_policy_flops():
    base = dict(vocab_size=100, block_size=32, n_embd=8, n_head=2,
                n_kv_heads=2, n_layer=2, up_dim=16, non_linearity="relu",
                pos_emb="learn", attn="mha")
    plain = M.step_flops(LLMConfig(**base), tokens_per_step=64, seq_len=32)
    block = M.step_flops(LLMConfig(**base, act_recomp=True,
                                   act_recomp_policy="block"),
                         tokens_per_step=64, seq_len=32)
    attn = M.step_flops(LLMConfig(**base, act_recomp=True,
                                  act_recomp_policy="attn"),
                        tokens_per_step=64, seq_len=32)
    # block remat re-runs the whole forward: 4/3 of the plain 3x-forward
    assert abs(block / plain - 4 / 3) < 1e-9
    # attention-only remat re-runs strictly less than the whole forward
    assert plain < attn < block


def test_flagship_flops_order_of_magnitude():
    """GPT-124M at 16384 tokens/step: ~6*N*tokens = ~1.2e13 FLOPs. The MFU
    denominator being off by 2x either way would misstate the headline."""
    cfg = flagship_gpt124m()
    flops = M.step_flops(cfg, tokens_per_step=16384, seq_len=1024)
    n_params = M.matmul_params_per_token(cfg)
    assert 110e6 < n_params < 135e6         # a true ~124M matmul census
    assert 0.9e13 < flops < 1.5e13

# ---------------------------------------------------------------------------
# one table of published peaks, keyed by the exact device_kind
# ---------------------------------------------------------------------------

class _FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_chip_spec_cpu_has_no_peak():
    """The CPU backend: no peak, MFU/MBU not computed — and the device-free
    planner names the v5e it plans for."""
    from distributed_pytorch_tpu.train import memplan
    assert M.chip_spec() is None
    assert M.peak_flops_per_chip() is None
    assert M.peak_hbm_bw_per_chip() is None
    assert M.mfu(flagship_gpt124m(), 16384, 1024, 0.3, 1) is None
    assert memplan.PLANNING_DEVICE_KIND == "TPU v5 lite"
    assert memplan.device_hbm_gb() == 16.0


def test_chip_spec_keys_are_exact(monkeypatch):
    """The v5e reports 'TPU v5 lite' and the v5p 'TPU v5': exact keys, so
    neither can land on the other's row (substring order used to decide)."""
    import jax
    for kind, flops, gib in (("TPU v5 lite", 197e12, 16.0),
                             ("TPU v5", 459e12, 95.0),
                             ("TPU v6 lite", 918e12, 32.0)):
        monkeypatch.setattr(jax, "devices",
                            lambda k=kind: [_FakeDevice("tpu", k)])
        assert M.chip_spec().peak_flops == flops
        assert M.chip_spec().hbm_gib == gib


def test_unknown_accelerator_kind_is_an_error(monkeypatch):
    """An accelerator that is not in the table is an ERROR in the MFU path
    and in the memory planner — never the v5e row by default."""
    import jax
    import pytest
    from distributed_pytorch_tpu.train import memplan
    monkeypatch.setattr(jax, "devices",
                        lambda: [_FakeDevice("tpu", "TPU v9 mega")])
    with pytest.raises(KeyError, match="TPU v9 mega"):
        M.peak_flops_per_chip()
    with pytest.raises(KeyError, match="CHIP_SPECS"):
        memplan.device_hbm_gb()


def test_peak_bytes_counts_the_reserved_region():
    """The counters a v5e reported after 6 dp steps of the 124M model at
    16x1024 (PR 21): the program's 14 GB of temporaries sit in the RESERVED
    region, outside peak_bytes_in_use."""
    st = {"bytes_in_use": 1515849728, "peak_bytes_in_use": 1515982336,
          "bytes_reserved": 14010826752, "peak_bytes_reserved": 14010826752,
          "bytes_limit": 16909334528}
    assert M._peak_bytes(st) == 1515982336 + 14010826752
    # a backend that keeps no reserved region: in-use alone
    assert M._peak_bytes({"peak_bytes_in_use": 5}) == 5
    assert M._peak_bytes({}) is None and M.device_memory_gb() is None
