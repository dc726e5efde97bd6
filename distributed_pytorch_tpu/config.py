"""Configuration dataclasses + CLI override system.

Reference parity: `LLMconfig` (reference single-gpu/model.py:39-75) and
`Trainconfig` (reference single-gpu/train.py:29-44), plus the ~33-flag
argparse CLI and the generic "setattr onto whichever dataclass owns the
name" override loop (reference single-gpu/train.py:136-206). TPU-first
deltas:

* configs are frozen (hashable) so they can be closed over by `jax.jit`
  without retracing hazards; CLI overrides produce new instances via
  `dataclasses.replace` instead of mutating defaults in place.
* `TrainConfig` grows TPU-native fields the reference spreads across five
  separate trainer scripts: `parallelism` (the named sharding recipe that
  replaces the reference's single/ddp/zero1/zero2/fsdp entry points),
  mesh axis sizes, and the compute dtype (bf16 on TPU; the reference's
  fp16 GradScaler machinery is unnecessary on TPU and intentionally
  absent — see SURVEY.md §5 "Mixed precision").
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from dataclasses import dataclass, field
from typing import Any, Callable, Literal, Optional

# ---------------------------------------------------------------------------
# Env-knob registry (ISSUE 12). Every tunable the package reads from the
# process environment is declared HERE — name, default, parser, one-line
# doc — and read through `knob()`. Modules never touch os.environ
# directly (scripts/lint.py's env-read rule enforces this), so the full
# tunable surface is one table: `python -m distributed_pytorch_tpu
# --knobs` prints it. Values are parsed PER READ (never cached here) so
# tests can monkeypatch the environment; modules that want
# import-time freezing (kernel tile sizes) assign the result to a module
# constant exactly as before.
# ---------------------------------------------------------------------------

def _onoff(s: str) -> str:
    v = s.strip().lower()
    if v not in ("auto", "on", "off"):
        raise ValueError(f"expected auto|on|off, got {s!r}")
    return v


@dataclass(frozen=True)
class Knob:
    """One registered environment tunable."""

    name: str
    default: str                       # raw string, parsed like an env read
    parse: Callable[[str], Any]
    doc: str

    def read(self) -> Any:
        """Parsed value: the process env var when set, else the default."""
        raw = os.environ.get(self.name)
        if raw is None:
            raw = self.default
        return self.parse(raw)


ENV_KNOBS: dict[str, Knob] = {}


def register_knob(name: str, default: str, parse: Callable[[str], Any] = str,
                  doc: str = "") -> Knob:
    k = Knob(name, default, parse, doc)
    ENV_KNOBS[name] = k
    return k


def knob(name: str) -> Any:
    """Read one registered knob (KeyError on unregistered names — typos
    fail loudly instead of silently defaulting)."""
    return ENV_KNOBS[name].read()


def knobs_table() -> str:
    """Human-readable registry dump (the --knobs CLI payload): name,
    default, current value (* when the env overrides), doc."""
    rows = [("KNOB", "DEFAULT", "CURRENT", "DOC")]
    for k in sorted(ENV_KNOBS.values(), key=lambda k: k.name):
        cur = k.read()
        mark = "*" if os.environ.get(k.name) is not None else ""
        rows.append((k.name, k.default, f"{cur}{mark}", k.doc))
    w0 = max(len(r[0]) for r in rows)
    w1 = max(len(r[1]) for r in rows)
    w2 = max(len(r[2]) for r in rows)
    return "\n".join(f"{r[0]:<{w0}}  {r[1]:<{w1}}  {r[2]:<{w2}}  {r[3]}"
                     for r in rows)


# --- kernel tile sizes (read at import by their owner modules). The
# flash-attention tiles and the scoped-VMEM limit are constants since the
# chip chose them (ops/flash_attention.py, compat.py); each of these
# becomes one with the ROADMAP item that measures it (R2, S5) ---
register_knob("GMM_BLOCK_M", "128", int,
              "grouped-matmul token-row tile (ops/grouped_matmul.py)")
register_knob("GMM_BLOCK_N", "512", int,
              "grouped-matmul out-feature tile")
register_knob("GMM_BLOCK_K", "512", int,
              "grouped-matmul contraction tile")
# the CONTIGUOUS decode kernel's tile; the paged kernels' tile is a pool
# block and the paged float kernel sizes its own ring (`_walk_shape`)
register_knob("FLASH_DECODE_BLOCK", "512", int,
              "flash-decode kv-length tile (ops/flash_decode.py)")

# --- auto|on|off feature gates (read per call; tests monkeypatch env) ---
register_knob("FLASH_DECODE", "auto", _onoff,
              "split-KV flash decode kernel gate")
register_knob("OVERLAP", "", lambda s: s.strip().lower(),
              "collective-matmul overlap rings: on|off|auto; empty defers "
              "to TrainConfig.overlap (ops/collective_matmul.py)")
register_knob("OVERLAP_RING", "bidir", lambda s: s.strip().lower(),
              "overlap ring direction: bidir | uni (A/B legs)")
register_knob("QUANT_KV", "auto",
              lambda s: _onoff(s) if s.strip() else "auto",
              "int8 KV-cache gate (ops/quant.py)")
register_knob("QUANT_W", "auto",
              lambda s: _onoff(s) if s.strip() else "auto",
              "int8 weight-matmul gate")
register_knob("SPEC_DECODE", "auto",
              lambda s: _onoff(s) if s.strip() else "auto",
              "self-speculative decoding gate (engine/decode.py; greedy "
              "engines only — temperature>0 falls back to the plain step)")
register_knob("SPEC_K", "4", lambda s: int(s) if s.strip() else 4,
              "speculative draft length: tokens the n-gram drafter "
              "proposes per step (verify runs K+1 positions)")
register_knob("KV_HOST_TIER", "auto",
              lambda s: _onoff(s) if s.strip() else "auto",
              "host-RAM KV second-tier gate (ops/kv_tier.py): evicted "
              "prefix blocks demote to host RAM and promote back on a "
              "radix hit; auto = on iff KV_HOST_BLOCKS > 0")
register_knob("KV_HOST_BLOCKS", "0", lambda s: int(s) if s.strip() else 0,
              "host-tier budget in KV blocks (0 with KV_HOST_TIER=on "
              "defaults to the HBM pool size; serve CLI --kv-host-gb "
              "prices GB into blocks via train/memplan.py)")
register_knob("KV_TIER_DIGEST_K", "8", lambda s: int(s) if s.strip() else 8,
              "radix-prefix digest width: top-k chain digests by cached "
              "depth a replica advertises for cache-aware routing")

# --- observability / fault injection ---
register_knob("TRACE", "on",
              lambda s: s.lower() not in ("off", "0", ""),
              "request-trace recorder enable (obs/trace.py)")
register_knob("TRACE_CAPACITY", "8192", int,
              "span-ring capacity of the process-default TraceRecorder")
register_knob("TRACE_GUARD", "warn", lambda s: s.strip().lower() or "warn",
              "retrace-guard violation handling: warn | strict | off "
              "(obs/retrace.py)")
register_knob("TRAIN_POISON_IT", "-1", int,
              "NaN-bomb iteration k's loss+grads (anomaly-guard fault "
              "injection, train/step.py)")

# --- multi-process topology announcements (train/loop.py reads these to
# decide whether jax.distributed.initialize is required; empty = unset) ---
register_knob("JAX_COORDINATOR_ADDRESS", "", str,
              "explicit multi-process coordinator host:port")
register_knob("JAX_NUM_PROCESSES", "", str,
              "explicit multi-process world size")
register_knob("JAX_PROCESS_ID", "", str,
              "this host's process id in the explicit topology")
register_knob("TPU_WORKER_HOSTNAMES", "", str,
              "Cloud TPU pod metadata: comma-separated worker hosts")
register_knob("MEGASCALE_COORDINATOR_ADDRESS", "", str,
              "multislice (megascale) coordinator announcement")

# --- elastic training (train/supervisor.py + train/checkpoint.py, ISSUE 13) ---
register_knob("CKPT_VERIFY", "on",
              lambda s: s.lower() not in ("off", "0", ""),
              "deep blake2b manifest verification on checkpoint restore "
              "(train/checkpoint.py); off = structural checks only")
register_knob("TRAIN_KEEP_CKPTS", "0", int,
              "checkpoint retention: keep the newest K verified step dirs, "
              "prune older ones after each save; 0 = keep everything "
              "(TrainConfig.keep_ckpts overrides when > 0)")
register_knob("SUPERVISOR_HB_FILE", "", str,
              "heartbeat file path the supervisor assigns a train worker; "
              "a worker writes liveness JSON there every interval")
register_knob("SUPERVISOR_HB_INTERVAL_S", "0.5", float,
              "seconds between worker heartbeat writes")
register_knob("SUPERVISOR_CPU_DEVICES", "0", int,
              "virtual CPU devices a supervisor-spawned worker requests "
              "before importing jax (compat.request_cpu_devices); 0 = off")

# --- fleet observability (serve/router.py, obs/slo.py, obs/replay.py,
# ISSUE 14) ---
register_knob("FLEET_POLL_INTERVAL_S", "1.0", float,
              "min seconds between the router's /metrics.json federation "
              "pulls per replica (rides the health-probe cadence)")
register_knob("SLO_TTFT_P99_S", "0.5", float,
              "TTFT p99 latency SLO threshold in seconds (a "
              "LATENCY_BUCKETS edge keeps bucket counting exact)")
register_knob("SLO_ITL_P99_S", "0.05", float,
              "ITL p99 latency SLO threshold in seconds (a "
              "LATENCY_BUCKETS edge keeps bucket counting exact)")
register_knob("SLO_AVAILABILITY", "0.999", float,
              "availability objective: completed/(completed+shed+failed)")
register_knob("SLO_WINDOWS_S", "300,3600",
              lambda s: tuple(float(x) for x in s.split(",") if x.strip()),
              "comma-separated burn-rate windows in seconds")
register_knob("OBS_REPORT_MAX_MAE_PCT", "20", float,
              "obs_report acceptance bar: max median absolute pct error "
              "of the fitted step-time model before the fit is flagged")

# --- static analysis (parallel/commscheck.py, ISSUE 15) ---
register_knob("COMMSCHECK_TRACE", "auto",
              lambda s: s.strip().lower() or "auto",
              "commscheck jaxpr-trace scope: auto (124M cells fully, "
              "ladder rungs at representative recipes) | full (every "
              "matrix cell — minutes) | off (spec-derived model only)")
register_knob("COMMSCHECK_DEVICES", "8", int,
              "virtual CPU devices the commscheck CLI requests before "
              "touching a backend (compat.request_cpu_devices); the "
              "default fits the 4x2 matrix meshes")

# --- pipeline schedule + optimizer offload (ISSUE 19) ---
register_knob("PP_SCHEDULE", "", lambda s: s.strip().lower(),
              "pipeline schedule override: carry | 1f1b | auto; empty "
              "defers to LLMConfig.pp_schedule (models/pipeline.py)")
register_knob("PP_VPP", "0", lambda s: int(s) if s.strip() else 0,
              "virtual chunks per pipeline stage for the 1f1b schedule; "
              "0 defers to LLMConfig.pp_vpp (0 = auto: n_layer/pp_stages, "
              "i.e. one-layer chunks, the maximally interleaved schedule)")
register_knob("OFFLOAD", "", lambda s: _onoff(s) if s.strip() else "",
              "ZeRO-Offload gate override: on | off | auto; empty defers "
              "to TrainConfig.offload (train/offload.py — AdamW moments "
              "in host RAM, update computed on host)")

# --- AOT program store (parallel/aot_store.py, ISSUE 18) ---
register_knob("AOT_STORE", "auto",
              lambda s: _onoff(s) if s.strip() else "auto",
              "AOT-compiled program store gate: on | off | auto (auto = "
              "on iff AOT_STORE_DIR is set); hit = deserialize a stored "
              "executable, miss = JIT + write back")
register_knob("AOT_STORE_DIR", "", str,
              "AOT store directory (empty with AOT_STORE=on defaults to "
              "runs/aot_store); one .bin executable + .json manifest per "
              "content-addressed program key")
register_knob("AOT_STRICT", "off", lambda s: s.strip().lower() or "off",
              "AOT store miss handling: off (compile + write back, "
              "counted) | warn (log each compile) | require (raise — a "
              "miss, or a stored program that rejects its inputs, is an "
              "error: the zero-cold-start CI proof)")


# --- control plane: SLO classes, tenant fairness, autoscaler
# (serve/control.py, sim/fleetsim.py, ISSUE 20) ---
def _slo_class(s: str) -> str:
    v = s.strip().lower()
    if v not in ("interactive", "batch"):
        raise ValueError(f"expected interactive|batch, got {s!r}")
    return v


register_knob("SLO_CLASS_DEFAULT", "interactive", _slo_class,
              "SLO class assumed when a request names none "
              "(X-SLO-Class header / 'slo_class' body field): "
              "interactive | batch")
register_knob("SLO_BATCH_RESUME_TIMEOUT_S", "0",
              lambda s: float(s) if s.strip() else 0.0,
              "max seconds a preemption-requeued batch request may wait "
              "for re-admission before an explicit "
              "ShedError(preempted_batch_timeout); 0 = never (resumed "
              "batch waits out any interactive burst, lossless)")
register_knob("TENANT_RATE_TOKENS_S", "0",
              lambda s: float(s) if s.strip() else 0.0,
              "per-tenant token-bucket refill rate in requests/s at the "
              "router (X-Tenant-Id); 0 = fairness off (every tenant "
              "admitted)")
register_knob("TENANT_BURST", "32",
              lambda s: float(s) if s.strip() else 32.0,
              "per-tenant token-bucket burst capacity (requests) — the "
              "headroom a tenant may spend above its steady rate")
register_knob("AUTOSCALE", "off", _onoff,
              "router autoscaler gate: on | off | auto (auto = on iff a "
              "replica launcher is configured); watches burn rates + "
              "occupancy forecasts and drives add/remove_replica")
register_knob("AUTOSCALE_MIN_REPLICAS", "1", int,
              "autoscaler floor: never scale the fleet below this")
register_knob("AUTOSCALE_MAX_REPLICAS", "8", int,
              "autoscaler ceiling: never scale the fleet above this")
register_knob("AUTOSCALE_LEAD_S", "15",
              lambda s: float(s) if s.strip() else 15.0,
              "scale-up lead time in seconds: the autoscaler acts on the "
              "demand forecast this far ahead, so a warmed-AOT replica "
              "(spinup < lead) is serving before the shed knee")
register_knob("AUTOSCALE_KNEE_OCCUPANCY", "0.85",
              lambda s: float(s) if s.strip() else 0.85,
              "occupancy at the shed knee (PERF.md occupancy-vs-shed "
              "curve): the autoscaler targets capacity that keeps "
              "forecast occupancy below this")
register_knob("AUTOSCALE_COOLDOWN_S", "5",
              lambda s: float(s) if s.strip() else 5.0,
              "min seconds between autoscaler actions (hysteresis "
              "against probe-noise flapping)")
register_knob("SIM_REPLICAS", "100", int,
              "fleet simulator: initial simulated replica count "
              "(sim/fleetsim.py)")
register_knob("SIM_DURATION_S", "120",
              lambda s: float(s) if s.strip() else 120.0,
              "fleet simulator: simulated seconds per scenario run")
register_knob("SIM_SEED", "0", int,
              "fleet simulator: base RNG seed (arrivals, prompt/budget "
              "draws, bootstrap resampling)")
register_knob("SIM_BOOT_S", "2.0",
              lambda s: float(s) if s.strip() else 2.0,
              "fleet simulator: spin-up seconds for an autoscaled "
              "replica (warmed-AOT start->first-token; PERF.md round 22)")


# --- persistent compilation cache (one placement rule for every entry
# point: trainer, serve and sample CLIs, benchmark/run.py, chip_smoke
# children, tests/conftest.py) ---
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable_compile_cache():
    """Place JAX's persistent compilation cache; returns the directory in
    force. With JAX_COMPILATION_CACHE_DIR set, JAX's own handling of that
    variable stands and nothing is set here; otherwise the cache lives at
    `<checkout>/.jax_cache` — a FIXED path (the path is part of the cache
    key: a per-pid or temp directory never hits), gitignored. Call before
    the first compile; touches no backend."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


ACTIVATIONS = (
    "relu", "gelu", "swish", "mish", "silu", "selu", "celu", "elu",
    "glu", "sigmoid", "lrelu", "tanh", "swiglu", "relu2",
)

ATTENTION_KINDS = ("mha", "mqa", "gqa", "mla")
ROUTER_KINDS = ("sigmoid", "softmax_topk")   # of a patterned model's 'E'
#: What a layer of each kind of `LLMConfig.layer_pattern` keeps for a
#: sequence between calls: "pools" (every row of its history, in blocks of
#: the paged pool), "window" (a ring of the last rows, a slot), "slot_state"
#: (leaves with a row a slot: a state-space layer's state and tail, a
#: convolution's tail, a linear-attention layer's matrix-valued state a head
#: and tail). A 'P' layer keeps two of them, in a cache slot
#: keyed by these names (models/gpt.py init_paged_cache). A latent layer
#: ('L') keeps pools too, of another row: one latent row a position with no
#: head axis (ops/latent_attention.py), addressed by the same block table.
#: Layers of different letters stand in ONE cache tree, a leaf a layer:
#: per-slot leaves ('M', 'C', 'K', 'G') beside pools ('*', 'L') beside rings
#: ('W'); the one rule between them is the 'L' assertion's below (a latent
#: pool and a GQA pool differ in row, so not both in one pattern).
LAYER_KEEPS = {"M": ("slot_state",), "C": ("slot_state",), "E": (), "F": (),
               "*": ("pools",), "W": ("window",),
               "P": ("pools", "slot_state"), "L": ("pools",),
               "K": ("slot_state",), "G": ("slot_state",)}
#: what `LLMConfig.attn_gate` may say: no gate, a gate a query HEAD from a
#: leaf of its own (`True` reads as 'head'), a gate a CHANNEL that shares
#: the query's projection
ATTN_GATE_KINDS = (False, True, "head", "channel")
POS_EMB_KINDS = ("learn", "sin", "rope", "none")
# The reference realizes these as five separate trainer scripts
# (single-gpu/train.py, multi-gpu/ddp/train.py, kaggle-zero1.py,
# kaggle-zero2.py, kaggle-fsdp.py); here each is a sharding recipe name.
# 'tp', 'ep', 'sp', and combinations exceed the reference (its README.md:7
# names them as unrealized goals).
PARALLELISM_RECIPES = (
    "single", "dp", "zero1", "zero2", "fsdp", "tp", "fsdp_tp", "ep", "sp",
    "pp",
)


@dataclass(frozen=True)
class LLMConfig:
    """Model hyperparameters. Mirrors reference `LLMconfig` field-for-field
    (single-gpu/model.py:39-75); frozen+hashable for jit."""

    # token params
    vocab_size: int = 50304
    block_size: int = 1024
    n_embd: int = 256
    pos_emb: str = "rope"  # Literal['learn','sin','rope']

    # feed-forward network
    up_dim: int = 384
    non_linearity: str = "swiglu"  # see ACTIVATIONS
    dropout: float = 0.0
    n_layer: int = 6

    # MoE (DeepSeekMoE; reference single-gpu/model.py:409-506)
    moe: bool = False
    n_exp: int = 16
    n_shared: int = 2
    n_act: int = 8          # INCLUDES the shared experts
    coeff: float = 0.01     # classic aux-loss coefficient
    aux_free: bool = True   # aux-loss-free balancing (bias-based)
    alpha: float = 1e-4     # complementary seq-wise aux loss coeff
    gamma: float = 1e-3     # bias update speed
    # routed-expert dispatch: 'dense' evaluates every routed expert on every
    # token (semantics oracle, no token dropping; fine for few experts);
    # 'scatter' is the capacity-bounded sort-based dispatch (EP-shardable,
    # O(active) FLOPs — the reference's O(active) Python loop equivalent,
    # single-gpu/model.py:489-506, made static-shape for XLA — but drops
    # assignments past capacity); 'grouped' is the dropless Pallas ragged
    # grouped-matmul dispatch (ops/grouped_matmul.py — O(active) FLOPs AND
    # zero drops; falls back to 'dense' where the kernel can't run)
    moe_impl: str = "dense"
    capacity_factor: float = 2.0  # scatter: per-expert slots = cf * N*k/E

    # attention
    attn: str = "gqa"  # Literal['mha','mqa','gqa','mla']
    n_head: int = 8
    n_kv_heads: int = 4
    # MLA only (defaults match reference ModelConfig, train.py:128-131, so
    # `--attn mla` works out of the box). TWO latent attentions read them.
    # The classic block's (`layer_pattern` empty; models/attention.py
    # NaiveMLA / FullMLA, the reference's teaching version): ONE
    # `head_size` for the content, rotary-free and value widths, no norm
    # on either latent, decode over a gathered copy of the latent view. The
    # pattern's 'L' layer (models/attention.py LatentAttention, the
    # published DeepSeek-V2/V3 form; `q_latent_dim` 0 there = no query
    # latent, `q = h W_q` in one matrix): an RMSNorm on each latent, a head's
    # query `[qk_nope_head_dim | rope_head_dim]` wide and its value
    # `v_head_dim` (0 = `head_size`, each), ONE rotated key head of
    # `rope_head_dim` shared by all query heads, the scale 1 / sqrt(nope +
    # rope), a paged pool of latent rows with a decode and a chunk kernel
    # of its own (ops/latent_attention.py).
    q_latent_dim: Optional[int] = 32
    kv_latent_dim: Optional[int] = 32
    rope_head_dim: Optional[int] = 16
    qk_nope_head_dim: int = 0
    v_head_dim: int = 0

    # memory subsystem: activation recomputation (jax.remat). Two
    # granularities, mirroring the reference's two variants: 'block' remats
    # whole transformer Blocks (module model.py:677-680); 'attn' remats
    # ONLY the attention sublayer (kaggle-ddp.py:526-534 — "memory grows
    # O(T^2) for attn, O(T) for MoE"), the memory-relevant one on TPU.
    act_recomp: bool = False
    act_recomp_policy: str = "block"  # 'block' | 'attn'

    # loss path: 'fused' computes CE blockwise over T without materializing
    # the (B, T, V) logits (ops/losses.py — the round-3 MFU fix);
    # 'unchunked' is the full-logits semantics oracle. Which of them a
    # program ran, and under what mesh, is `ops/losses.py tied_head_loss`'s
    # to say. loss_chunk: T-chunk size for 'fused', 0 = auto.
    loss_impl: str = "fused"
    loss_chunk: int = 0

    # pipeline parallelism (models/pipeline.py; the last member of the
    # reference's "5D parallelism" goal, README.md:7). pp_stages > 1 stacks
    # the transformer blocks on a leading layer axis (sharded over the
    # 'pipe' mesh axis) and streams pp_microbatches batch slices through a
    # pipeline schedule. 0 microbatches = auto (2 * stages).
    # pp_schedule picks that schedule: 'carry' is the per-layer carry
    # (all L layers every tick on an (L, ...) buffer); '1f1b' is the
    # interleaved-1F1B schedule (each stage holds pp_vpp virtual chunks,
    # bubble ~ (S-1)/(vpp*M)); 'auto' = 1f1b for dense models, carry for
    # MoE (whose per-tick load-stats masking only the carry path carries).
    # pp_vpp: virtual chunks per stage for 1f1b; 0 = auto (n_layer /
    # pp_stages — one-layer chunks, the carry schedule's granularity).
    pp_stages: int = 1
    pp_microbatches: int = 0
    pp_schedule: str = "auto"  # 'auto' | 'carry' | '1f1b'
    pp_vpp: int = 0

    # a per-layer pattern of blocks `x + mixer(norm(x))`, one character
    # a layer: 'M' a Mamba-2 state-space mixer (models/ssm.py),
    # 'C' a gated short-convolution mixer (models/shortconv.py), 'E' routed
    # experts of which this chip holds a share (models/mlp.py
    # RoutedExperts; `router` says how), 'F' a dense FFN of its own width
    # `dense_up_dim` (models/mlp.py MLP), '*' attention (GQA), 'W'
    # attention over a window of the last `window` positions (GQA at
    # `window_heads` query heads; ops/window_attention.py), 'P' TWO
    # mixers side by side on the one normed input, a Mamba-2 mixer and
    # GQA, `x + a_out * attn(a_in * h) + s_out * ssm(s_in * h)` (the
    # multipliers below; models/gpt.py MixerBlock): the one kind whose
    # cache slot is of two kinds, a slot's state and tail AND blocks of
    # the pool; 'L' latent attention (models/attention.py LatentAttention:
    # `attn` 'mla', the widths beside it above), whose pools hold one
    # latent row a position; 'K' delta-rule linear attention with a decay
    # a channel (KDA; models/linear_attention.py, the `kda_*` widths
    # below), whose slot keeps a (heads, d_k, d_v) float32 state and a
    # convolution tail; 'G' the gated delta rule with a decay a HEAD
    # (Gated DeltaNet; models/linear_attention.py GatedDeltaNet, the
    # `gdn_*` widths below), fewer key heads than value heads, a slot's
    # state and tail of the same kind. Empty = the attention + FFN block
    # above for every layer. `n_layer` is its length. A patterned model has
    # RMSNorms, no FFN biases, and its parameters are created in
    # `LLM.param_dtype`.
    layer_pattern: str = ""
    norm_eps: float = 1e-5       # the RMSNorms of a patterned model
    # `x_hat * (1 + w)` in place of `x_hat * w`: every block's norm, the
    # final norm and the QK-norms (a mixer's own output norm keeps `w`)
    norm_zero_centred: bool = False
    tie_head: bool = True        # False: an `lm_head` (V, C) of its own
    head_dim: int = 0            # attention head size; 0 = n_embd // n_head
    attn_bias: bool = True       # biases on the qkv and output projections
    # '*' layers of a patterned model: `qk_norm` puts an RMSNorm with one
    # learned head-size vector over every q head and every k head, before
    # the positions; with `pos_emb` 'rope' q and k are rotated at the
    # slots' own positions and keys are cached rotated. `rope_theta` is
    # the angles' base and `rope_pairing` which lanes turn together, in
    # any model: 'adjacent' (2i with 2i + 1, the reference's) or 'half'
    # (i with i + head size / 2, the published `rotate_half` of the
    # Hugging Face families); ops/rope.py
    qk_norm: bool = False
    rope_theta: float = 10000.0
    rope_pairing: str = "adjacent"
    # the '*' layers' angles beyond the plain ones (ops/rope.py):
    # `rotary_frac` of a head's lanes, the first ones, are rotated and
    # the rest pass; a `rope_factor` over 1 is YaRN's: every frequency is
    # blended between itself and itself over the factor by where its
    # wavelength lies against `rope_original_len` (`ops/rope.py`), and
    # cos and sin are multiplied by `rope_attn_factor`. `attn_gate`
    # (`ATTN_GATE_KINDS`): True or 'head', a gate a query head, sigmoid of
    # a linear map of the block's normed input (leaf `c_gate`), on the
    # head's output before `c_proj`; 'channel', a gate a CHANNEL of every
    # head, the sigmoid of as many further columns of the query's own
    # projection (`c_attn` = [q | k | v | gate]); in '*' and 'W' layers
    # alike.
    rotary_frac: float = 1.0
    rope_factor: float = 1.0
    rope_original_len: int = 0
    rope_attn_factor: float = 1.0
    attn_gate: Any = False
    # 'W' layers: a query at position i sees keys j with 0 <= i - j <
    # `window`, its own included; `window_heads` query heads over the
    # same `n_kv_heads`; plain RoPE over all lanes at `window_rope_theta`.
    # What such a layer keeps a slot is a ring of the window's rows, not
    # blocks of the pool (models/gpt.py init_paged_cache).
    window: int = 0
    window_heads: int = 0
    window_rope_theta: float = 10000.0
    # 'E' layers: `n_exp` - `n_shared` is the ROUTER's width and `n_act` -
    # `n_shared` its top-k, as above; `experts_held` = (first id, count) is
    # the slice of routed experts this chip holds (empty: all), what the
    # others would add is left out; `shared_up_dim` the shared expert's
    # width (0 = up_dim); `routed_scale` multiplies the renormalised
    # weights. `router`: 'sigmoid' (scores sigmoid over every expert, the
    # top k of score + correction bias, weights renormalised) or
    # 'softmax_topk' (the top k LOGITS, softmax over those k, no bias and
    # no scale). A gated `non_linearity` ('swiglu', 'glu') makes the routed
    # and the shared experts gated: an up stack of 2 x up_dim, [a | b].
    # `n_group` > 1 limits the sigmoid router's choice to groups (the
    # `deepseek_v3` rule): the routed experts are `n_group` groups of
    # consecutive ids, a group scores the sum of its two largest s + b, the
    # `topk_group` best groups are kept and the top k taken inside them
    # (models/mlp.py route_sigmoid); 1 = no limit, the ops as without it.
    # `shared_gate`: the shared expert's output times sigmoid(h w_sg), one
    # scalar a token (leaf `shared_gate`, (C, 1)).
    experts_held: tuple = ()
    shared_up_dim: int = 0
    shared_gate: bool = False
    routed_scale: float = 1.0
    router: str = "sigmoid"
    n_group: int = 1
    topk_group: int = 1
    # a patterned model's scalar multipliers (1 = the program is as
    # without them): the embedding's output times `embed_mult`, every
    # block `x + resid_mult * mixer(norm(x))`, attention's softmax at
    # `attn_scale` (0 = 1/sqrt(head size)), the logits over `logits_div`
    embed_mult: float = 1.0
    resid_mult: float = 1.0
    attn_scale: float = 0.0
    logits_div: float = 1.0
    # the published multipliers of a model parametrised for width
    # transfer, each applied where it is published and in float32
    # (ops/mup.py): a 'P' block's branches take `attn_in_mult` / `ssm_in_mult`
    # times the normed input and add `attn_out_mult` / `ssm_out_mult`
    # times their output; every GQA's keys are `key_mult` times W_k u,
    # before the positions and the cache; `ssm_mults` = five numbers
    # spread over the segments [z | x | B | C | dt] of a Mamba-2
    # in-projection's output (empty: none); an 'F' block is
    # `mlp_down_mult * W_down(silu(mlp_gate_mult * W_gate u) * W_up u)`
    attn_in_mult: float = 1.0
    attn_out_mult: float = 1.0
    key_mult: float = 1.0
    ssm_in_mult: float = 1.0
    ssm_out_mult: float = 1.0
    ssm_mults: tuple = ()
    mlp_gate_mult: float = 1.0
    mlp_down_mult: float = 1.0
    # 'M' layers (Mamba-2): heads x head size = d_inner, groups share B/C
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # 'C' layers: the depthwise convolution's length (published
    # `conv_L_cache`); a slot carries its last `conv_len` - 1 inputs.
    # 'F' layers: the dense FFN's width, gated as `non_linearity` says
    conv_len: int = 3
    dense_up_dim: int = 0
    # 'K' layers (KDA): `kda_heads` heads of a (`kda_head_dim`,
    # `kda_head_dim`) state, d_k = d_v; a depthwise convolution of
    # `kda_conv` taps over [q' | k' | v'] (a slot carries its last
    # `kda_conv` - 1 rows); the log decay a channel is `kda_lower_bound` x
    # sigmoid(...), so it lies in (`kda_lower_bound`, 0): the bound is what
    # lets a chunked form invert a span's decay in float32
    # (ops/delta_rule.py). One sigmoid output gate a head.
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_lower_bound: float = -5.0
    # 'G' layers (Gated DeltaNet): `gdn_heads` VALUE heads of a
    # (`gdn_head_dim`, `gdn_head_dim`) state over `gdn_key_heads` key heads
    # (value head j reads key head j // (heads / key heads)); a depthwise
    # convolution of `gdn_conv` taps over [q' | k' | v']; ONE log decay a
    # value head, -exp(A_log) softplus(.), with no lower bound: the chunked
    # form takes exp(c_i - c_j), i >= j, of the cumulative decay and never
    # inverts one (ops/delta_rule.py gdn_chunk). A silu gate a channel.
    gdn_heads: int = 0
    gdn_key_heads: int = 0
    gdn_head_dim: int = 0
    gdn_conv: int = 4

    def __post_init__(self):
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        object.__setattr__(self, "ssm_mults", tuple(self.ssm_mults))
        assert len(self.ssm_mults) in (0, 5), \
            "ssm_mults: one number a segment of [z | x | B | C | dt]"
        assert self.rope_pairing in ("adjacent", "half"), self.rope_pairing
        assert self.attn_gate in ATTN_GATE_KINDS, \
            f"attn_gate {self.attn_gate!r} is none of {ATTN_GATE_KINDS}"
        assert self.rope_factor >= 1.0, "rope_factor is at least 1"
        assert self.rope_factor == 1.0 or self.rope_original_len > 0, \
            "yarn (a rope_factor over 1) needs rope_original_len"
        if self.layer_pattern:
            assert set(self.layer_pattern) <= set(LAYER_KEEPS), \
                self.layer_pattern
            assert len(self.layer_pattern) == self.n_layer, (
                f"layer_pattern has {len(self.layer_pattern)} layers, "
                f"n_layer is {self.n_layer}")
            assert self.pp_stages == 1 and not self.moe
            if set("MP") & set(self.layer_pattern):
                assert self.ssm_heads and self.ssm_head_dim and \
                    self.ssm_state and \
                    self.ssm_heads % self.ssm_groups == 0
            if "P" in self.layer_pattern:
                assert self.attn in ("mha", "mqa", "gqa"), \
                    "a 'P' layer's attention branch is GQA"
            if "C" in self.layer_pattern:
                assert self.conv_len >= 2
            if "K" in self.layer_pattern:
                assert self.kda_heads > 0 and self.kda_head_dim > 0 \
                    and self.kda_conv >= 2, \
                    "a 'K' layer needs `kda_heads`, `kda_head_dim` and a " \
                    "convolution of at least 2 taps"
                assert -88.0 / 16 < self.kda_lower_bound < 0.0, \
                    "the log decay's bound times a sub-chunk of 16 rows " \
                    "has to fit float32's exponent (ops/delta_rule.py)"
            if "G" in self.layer_pattern:
                assert self.gdn_heads > 0 and self.gdn_key_heads > 0 \
                    and self.gdn_head_dim > 0 and self.gdn_conv >= 2, \
                    "a 'G' layer needs `gdn_heads`, `gdn_key_heads`, " \
                    "`gdn_head_dim` and a convolution of at least 2 taps"
                assert self.gdn_heads % self.gdn_key_heads == 0, \
                    f"gdn_heads {self.gdn_heads} (value heads) is no " \
                    f"multiple of gdn_key_heads {self.gdn_key_heads}"
            else:
                assert not (self.gdn_heads or self.gdn_key_heads
                            or self.gdn_head_dim), \
                    "`gdn_*` widths without a 'G' layer"
            if "L" in self.layer_pattern:
                assert self.attn == "mla" and self.pos_emb == "rope" \
                    and not set("*WP") & set(self.layer_pattern), \
                    "a pattern with latent layers says attn 'mla', " \
                    "pos_emb 'rope', and has no GQA layer beside them " \
                    "('*', 'W', 'P'): any other kind may stand there " \
                    "(per-slot state 'M', 'C', 'K', 'G'; 'F'; 'E')"
                # `q_latent_dim` 0 (or None): no query latent, q = h W_q
                assert self.kv_latent_dim \
                    and self.rope_head_dim and self.rope_head_dim % 2 == 0
            else:
                assert self.attn != "mla", \
                    "a pattern's latent attention is its 'L' layers'"
            if "F" in self.layer_pattern:
                assert self.dense_up_dim > 0
            if "W" in self.layer_pattern:
                assert self.window > 0 and self.window_heads > 0, \
                    "a 'W' layer needs `window` and `window_heads`"
                assert self.window_heads % self.n_kv_heads == 0, \
                    "window_heads must be divisible by n_kv_heads"
                assert self.attn in ("mha", "mqa", "gqa") \
                    and self.pos_emb == "rope", \
                    "a 'W' layer is GQA with rotary positions"
            else:
                assert not self.window and not self.window_heads, \
                    "`window` / `window_heads` without a 'W' layer"
            frac = self.rotary_frac * self.head_size
            assert 0 < self.rotary_frac <= 1.0 and frac == int(frac) \
                and int(frac) % 2 == 0, \
                f"rotary_frac {self.rotary_frac} of a head of " \
                f"{self.head_size} is no even number of lanes"
            if "E" in self.layer_pattern:
                assert self.n_act > self.n_shared and \
                    self.n_exp > self.n_shared
                assert self.router in ROUTER_KINDS, self.router
                assert self.n_group >= 1 and \
                    1 <= self.topk_group <= self.n_group and \
                    self.n_routed % self.n_group == 0, \
                    "n_group divides the routed experts, topk_group of " \
                    "them are kept"
                if self.n_group > 1:
                    assert self.router == "sigmoid" and \
                        self.n_routed // self.n_group >= 2 and \
                        self.topk_group * (self.n_routed // self.n_group) \
                        >= self.n_act_routed, \
                        "a group limit is the sigmoid router's; a group " \
                        "scores its two best; the kept groups hold top k"
                if self.experts_held:
                    lo, n = self.experts_held
                    assert 0 <= lo and n >= 1 and lo + n <= self.n_routed
                assert not self.shared_gate or self.n_shared, \
                    "`shared_gate` gates a shared expert: n_shared is 0"
            else:
                assert not self.shared_gate, \
                    "`shared_gate` without an 'E' layer"
            assert not self.attn_gate or set("*WP") & set(
                self.layer_pattern), "`attn_gate` without a GQA layer"
        else:
            assert (self.embed_mult, self.resid_mult, self.attn_scale,
                    self.logits_div) == (1.0, 1.0, 0.0, 1.0) \
                and not self.ssm_mults and all(
                    m == 1.0 for m in (
                        self.attn_in_mult, self.attn_out_mult,
                        self.key_mult, self.ssm_in_mult, self.ssm_out_mult,
                        self.mlp_gate_mult, self.mlp_down_mult)), \
                "the multipliers are a patterned model's"
            assert not (self.qk_norm or self.norm_zero_centred
                        or self.shared_gate), \
                "QK-norm, the zero-centred norm and the shared expert's " \
                "gate are a patterned model's"
            assert not (self.qk_nope_head_dim or self.v_head_dim), \
                "separate nope and value widths are an 'L' layer's"
            assert not (self.window or self.window_heads or self.attn_gate
                        or self.rope_factor != 1.0
                        or self.rotary_frac != 1.0
                        or self.rope_attn_factor != 1.0), \
                "windows, gates and scaled RoPE are a patterned model's"
        # Cross-field normalization, mirroring reference
        # single-gpu/train.py:198-206 (mha -> n_kv_heads=n_head, mqa -> 1,
        # mla requires latent dims; rope-mla additionally rope_head_dim).
        if self.attn == "mha":
            object.__setattr__(self, "n_kv_heads", self.n_head)
        elif self.attn == "mqa":
            object.__setattr__(self, "n_kv_heads", 1)
        elif self.attn == "gqa":
            assert self.n_head % self.n_kv_heads == 0, \
                "n_head must be divisible by n_kv_heads"
        elif self.attn == "mla":
            assert self.kv_latent_dim is not None and (
                self.q_latent_dim is not None
                or "L" in self.layer_pattern), \
                "Either q_latent_dim or kv_latent_dim is missing"
            if self.pos_emb == "rope":
                assert self.rope_head_dim is not None, "Need dim of Rotary heads"
        else:
            raise ValueError(f"unknown attention kind {self.attn!r}")
        assert self.head_dim or self.n_embd % self.n_head == 0, \
            "n_embd must be divisible by n_head"
        assert self.pos_emb in POS_EMB_KINDS, f"unknown pos_emb {self.pos_emb!r}"
        assert self.non_linearity.lower() in ACTIVATIONS, \
            f"unknown non_linearity {self.non_linearity!r}"
        if self.moe:
            assert self.n_act > self.n_shared, \
                "Number of active experts must be greater than shared experts"
            assert self.n_exp > self.n_shared
            assert self.n_act <= self.n_exp, \
                "n_act (which includes shared experts) cannot exceed n_exp"
        assert self.moe_impl in ("dense", "scatter", "grouped"), \
            f"unknown moe_impl {self.moe_impl!r}"
        assert self.capacity_factor > 0
        assert self.act_recomp_policy in ("block", "attn"), \
            f"unknown act_recomp_policy {self.act_recomp_policy!r}"
        if self.loss_impl == "pallas":
            # a stored config may still name it: never run 'fused' under
            # the kernel's name
            raise ValueError(
                "loss_impl='pallas': the streaming CE kernel left the tree "
                "at PR 48; 'fused' is what ran faster in the train cell "
                "(loss_ms.train 27.85 against 34.72, PERF.md section 6): "
                "say loss_impl='fused'")
        assert self.loss_impl in ("fused", "unchunked"), \
            f"unknown loss_impl {self.loss_impl!r}"
        if self.loss_chunk > 0:
            # a non-dividing chunk would silently fall back to the
            # full-logits path — fail loudly at config time instead
            assert self.block_size % self.loss_chunk == 0, (
                f"loss_chunk {self.loss_chunk} must divide block_size "
                f"{self.block_size}")
        if self.pp_stages > 1:
            assert self.n_layer % self.pp_stages == 0, (
                f"pp_stages {self.pp_stages} must divide n_layer "
                f"{self.n_layer}")
        assert self.pp_schedule in ("auto", "carry", "1f1b"), \
            f"unknown pp_schedule {self.pp_schedule!r}"
        assert self.pp_vpp >= 0, "pp_vpp must be >= 0 (0 = auto)"
        if self.pp_vpp > 0 and self.pp_stages > 1:
            assert self.n_layer % (self.pp_stages * self.pp_vpp) == 0, (
                f"pp_stages*pp_vpp {self.pp_stages * self.pp_vpp} must "
                f"divide n_layer {self.n_layer}")

    @property
    def head_size(self) -> int:
        return self.head_dim or self.n_embd // self.n_head

    @property
    def attn_gate_kind(self) -> str:
        """'' | 'head' | 'channel' (`attn_gate` True reads 'head')."""
        return "head" if self.attn_gate is True else (self.attn_gate or "")

    @property
    def recurrent(self) -> bool:
        """Whether some layer keeps per-sequence state that is no block of
        the paged cache: a state-space or linear-attention layer's state
        and tail, or a convolution mixer's tail alone (what prefix reuse,
        the host tier and speculative roll-back cannot snapshot yet)."""
        return self.layers_keeping("slot_state") > 0

    @property
    def layer_keeps(self) -> tuple:
        """`LAYER_KEEPS` of every layer, in order; a classic model's
        layers each keep their history in the pools."""
        return tuple(LAYER_KEEPS[kind] for kind in
                     self.layer_pattern or "*" * self.n_layer)

    def layers_keeping(self, what: str) -> int:
        """How many layers keep `what` (a name of `LAYER_KEEPS`)."""
        return sum(what in keeps for keeps in self.layer_keeps)

    @property
    def slot_state(self) -> str:
        """What the layers keep a SLOT that is no block of the paged
        cache, in words ("" = nothing): the engine says it where prefix
        reuse, the host tier and speculation stand down for it."""
        kinds = [name for name, held in (
            ("recurrent layers", self.recurrent),
            ("window layers", self.layers_keeping("window"))) if held]
        return " and ".join(kinds)

    @property
    def n_routed(self) -> int:
        return self.n_exp - self.n_shared

    @property
    def n_act_routed(self) -> int:
        return self.n_act - self.n_shared


def flagship_gpt124m(**overrides) -> "LLMConfig":
    """The headline GPT-2-124M-class benchmark model (BASELINE.json north
    star; the config the reference's single-gpu/train.sh trains at
    block_size 1024). One definition shared by the `gpt2_124m` preset,
    scripts/profile_step.py and the driver entry (__graft_entry__.py).

    up_dim is 2048, not GPT-2's 3072: with the gated swiglu FFN the fused
    up projection is (C, 2*up_dim), so 2048 reproduces exactly GPT-2's
    4.7M FFN params/layer (the standard 2/3 scaling) and the model is a
    true ~124M. Rounds 1-3 benched up_dim=3072 (a 152M model labeled
    124M); MFU — the headline metric — is size-normalized either way."""
    base = dict(vocab_size=50304, block_size=1024, n_embd=768, n_head=12,
                n_kv_heads=12, attn="mha", n_layer=12, up_dim=2048,
                non_linearity="swiglu", pos_emb="rope")
    base.update(overrides)
    return LLMConfig(**base)


def _gpt2_preset(width: int, depth: int, heads: int, up: int,
                 **overrides) -> "LLMConfig":
    base = dict(vocab_size=50304, block_size=1024, n_embd=width,
                n_head=heads, n_kv_heads=heads, attn="mha",
                n_layer=depth, up_dim=up, non_linearity="swiglu",
                pos_emb="rope")
    base.update(overrides)
    return LLMConfig(**base)


def gpt2_350m(**overrides) -> "LLMConfig":
    """GPT-2 medium class (~351M with the gated-FFN 2/3 scaling:
    up_dim 2688 ~= 8*1024/3 rounded to a lane multiple, reproducing
    GPT-2's 8*C^2 FFN params/layer like flagship_gpt124m does).
    BASELINE.json ladder rung 1 — target recipes zero1/zero2."""
    return _gpt2_preset(1024, 24, 16, 2688, **overrides)


def gpt2_774m(**overrides) -> "LLMConfig":
    """GPT-2 large class (~769M; up_dim 3392 ~= 8*1280/3). Ladder rung 2 —
    target recipe fsdp."""
    return _gpt2_preset(1280, 36, 20, 3392, **overrides)


def gpt2_1p5b(**overrides) -> "LLMConfig":
    """GPT-2 XL class (~1.55B; up_dim 4224 ~= 8*1600/3; 25 heads of 64 as
    in GPT-2 XL). Ladder rung 3 — fsdp single-host, rung 4 two-host."""
    return _gpt2_preset(1600, 48, 25, 4224, **overrides)


def gpt2_7b(**overrides) -> "LLMConfig":
    """~6.7B Llama-7B-class rung (up_dim 10880 ~= 8*4096/3 rounded to a
    lane multiple; 32 heads of 128). The pod-scale exit-bar rung
    (ROADMAP): pp x fsdp x tp recipes with the interleaved-1F1B schedule
    and ZeRO-Offload — moments in host RAM — are what make it price under
    v5e 16 GiB/chip (train/memplan.py --offload prints the delta)."""
    return _gpt2_preset(4096, 32, 32, 10880, **overrides)


# name -> factory; the CLI's --preset flag, memplan, shardcheck and
# commscheck all resolve through this table so a rung cannot drift between
# them.
PRESETS = {
    "gpt2_124m": flagship_gpt124m,
    "gpt2_350m": gpt2_350m,
    "gpt2_774m": gpt2_774m,
    "gpt2_1p5b": gpt2_1p5b,
    "gpt2_7b": gpt2_7b,
}


@dataclass(frozen=True)
class TrainConfig:
    """Training hyperparameters. Mirrors reference `Trainconfig`
    (single-gpu/train.py:29-44) plus TPU-native parallelism fields."""

    dataset: str = "tinystories"  # Literal['shakespeare','tinystories','fineweb']
    data_dir: str = "data"
    total_batch_size: int = 2 ** 11   # in tokens
    batch_size: int = 2              # micro-batch size (sequences)
    max_iters: int = 2500
    eval: bool = False
    eval_interval: int = 100
    eval_iters: int = 100
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    optimizer: str = "adamw"         # adamw | lion | adafactor
                                     # (reference: AdamW only, model.py:619)
    save_model: bool = False
    save_stats: bool = True          # persist run stats as <ckpt>/stats.json
                                     # (reference `<name>_stats.pt`,
                                     # single-gpu/train.py:363-372)
    file_name: str = "llm_model"
    act_recomp: bool = False
    seed: int = 1729

    # --- TPU-native fields (no reference equivalent; replace the reference's
    # per-script hardcoding of AMP dtype and torchrun world topology) ---
    parallelism: str = "single"      # see PARALLELISM_RECIPES
    platform: str = "auto"           # auto | tpu | cpu — pin the JAX
                                     # backend before any device op (same
                                     # effect as JAX_PLATFORMS)
    dp_size: int = -1                # -1: infer from device count
    tp_size: int = 1                 # model axis size (tp / fsdp_tp)
    ep_size: int = 1                 # expert axis size (ep)
    sp_size: int = 1                 # sequence axis size (sp / ring attention)
    pp_size: int = 1                 # pipe axis size (pp; = LLMConfig.pp_stages)
    compute_dtype: str = "bfloat16"  # bf16 compute, fp32 params/opt state
    # attention kernel choice; under the 'sp' recipe, 'auto'/'zigzag'
    # select the load-balanced zig-zag ring over the 'seq' axis, 'ring'
    # the contiguous-layout ring, 'ulysses' the all-to-all head<->sequence
    # variant (ops/ring_attention.py)
    attn_impl: str = "auto"  # auto | xla | pallas | naive | ring | zigzag | ulysses
    moe_impl: str = "dense"          # 'dense' | 'scatter' | 'grouped'
    # collective-matmul overlap for the ZeRO-3 family
    # (ops/collective_matmul.py): 'on' fuses param all-gathers / grad
    # reduce-scatters into ppermute rings overlapped with the matmuls;
    # 'auto' keeps the known-good GSPMD schedule until a hardware number
    # exists. The OVERLAP env var overrides this field.
    overlap: str = "auto"            # auto | on | off
    # checkpoint/resume (exceeds reference save-only; SURVEY.md §5)
    ckpt_interval: int = 0           # 0 = end-of-run only
    resume: bool = False
    keep_ckpts: int = 0              # retention: keep newest K verified
                                     # step dirs, prune older after each
                                     # save; 0 defers to TRAIN_KEEP_CKPTS
                                     # knob (ISSUE 13)
    log_interval: int = 1
    profile: bool = False            # jax.profiler trace capture
    profile_dir: str = ""            # capture output dir; "" = the
                                     # obs/profile.py convention
                                     # runs/<file_name>/profile
    # --- training observability (train/telemetry.py, ISSUE 10) ---
    telemetry: bool = True           # train flight recorder + step-phase
                                     # timers; False = disabled mode (one
                                     # attribute check/step, no alloc)
    metrics_port: int = -1           # live /metrics+/debug/timeline+
                                     # /healthz HTTP thread on the main
                                     # host: -1 off, 0 ephemeral port
                                     # (logged), >0 fixed port
    anomaly: str = "warn"            # loss/grad guard: 'skip' withholds
                                     # the optimizer update on a NaN/inf
                                     # step, 'warn' records only, 'off'
    # ZeRO-Offload (train/offload.py, ISSUE 19): optimizer moments pinned
    # in host RAM, the update computed on host, parameters streamed back —
    # HBM pays params+grads+activations only, the optimizer costs PCIe
    # bandwidth. 'auto' = on iff memplan prices the in-HBM plan over
    # budget AND the offload plan under it; the OFFLOAD env knob
    # overrides this field.
    offload: str = "auto"            # auto | on | off

    def __post_init__(self):
        assert self.parallelism in PARALLELISM_RECIPES, \
            f"unknown parallelism recipe {self.parallelism!r}"
        assert self.moe_impl in ("dense", "scatter", "grouped"), \
            f"unknown moe_impl {self.moe_impl!r}"
        assert self.attn_impl in ("auto", "xla", "pallas", "naive", "ring",
                                  "zigzag", "ulysses"), \
            f"unknown attn_impl {self.attn_impl!r}"
        assert self.platform in ("auto", "tpu", "cpu"), \
            f"unknown platform {self.platform!r}"
        assert self.overlap in ("auto", "on", "off"), \
            f"unknown overlap mode {self.overlap!r}"
        assert self.optimizer in ("adamw", "lion", "adafactor"), \
            f"unknown optimizer {self.optimizer!r}"
        assert self.anomaly in ("skip", "warn", "off"), \
            f"unknown anomaly mode {self.anomaly!r}"
        assert self.offload in ("auto", "on", "off"), \
            f"unknown offload mode {self.offload!r}"


# ---------------------------------------------------------------------------
# CLI override system (reference single-gpu/train.py:136-206): one flag per
# dataclass field, routed generically to whichever config owns the name.
# ---------------------------------------------------------------------------

_BOOL_FLAGS = {
    # reference store_true flags (single-gpu/train.py:176-180)
    "moe", "aux_free", "eval", "save_model", "act_recomp",
    # new
    "resume", "profile", "save_stats", "telemetry",
}


def build_parser(model_defaults: LLMConfig | None = None,
                 train_defaults: TrainConfig | None = None) -> argparse.ArgumentParser:
    """Build an argparse parser exposing every field of both dataclasses.

    Mirrors reference parse_args() (single-gpu/train.py:136-181) including
    `--total_batch_size_str`, which accepts an expression like "2**14"
    (evaluated arithmetically, reference train.py:186-188)."""
    model_defaults = model_defaults or LLMConfig()
    train_defaults = train_defaults or TrainConfig()
    p = argparse.ArgumentParser(description="Train an LLM on TPU (JAX/XLA)")

    seen: set[str] = set()
    for cfg in (train_defaults, model_defaults):
        for f in dataclasses.fields(cfg):
            name = f.name
            if name in seen:  # act_recomp lives in both configs
                continue
            seen.add(name)
            if name == "total_batch_size":
                p.add_argument("--total_batch_size_str", type=str,
                               default=str(train_defaults.total_batch_size),
                               help="Total batch size in tokens, as an arithmetic "
                                    "expression, e.g. '2**14'")
                continue
            default = getattr(cfg, name)
            if name in _BOOL_FLAGS:
                if default:
                    # store_true can never turn a default-True flag off;
                    # expose --name / --no-name instead (e.g. --no-aux_free)
                    p.add_argument(f"--{name}", default=default,
                                   action=argparse.BooleanOptionalAction)
                else:
                    p.add_argument(f"--{name}", action="store_true",
                                   default=default)
            elif f.type in ("int", "Optional[int]", int):
                p.add_argument(f"--{name}", type=int, default=default)
            elif f.type in ("float", float):
                p.add_argument(f"--{name}", type=float, default=default)
            else:
                p.add_argument(f"--{name}", type=str, default=default)
    # non-dataclass driver flags (configs_from_args ignores unknown keys):
    p.add_argument("--preset", choices=sorted(PRESETS), default=None,
                   help="model-size preset (the 124M..1.5B ladder); "
                        "explicit flags still override its fields")
    p.add_argument("--dryrun", action="store_true", default=False,
                   help="print the static HBM plan (micro-batch, remat "
                        "policy, est. peak HBM, grad-accum) and the "
                        "shardcheck findings for the recipe, then exit "
                        "without training")
    p.add_argument("--knobs", action="store_true", default=False,
                   help="print the env-knob registry (name, default, "
                        "current value, doc) and exit")
    return p


def _safe_int_expr(s: str) -> int:
    """Arithmetic-only replacement for the reference's bare eval()
    (single-gpu/train.py:186-188)."""
    import ast
    node = ast.parse(s, mode="eval")
    allowed = (ast.Expression, ast.BinOp, ast.UnaryOp, ast.Constant,
               ast.Add, ast.Sub, ast.Mult, ast.Div, ast.FloorDiv, ast.Pow,
               ast.USub, ast.Mod)
    for n in ast.walk(node):
        if not isinstance(n, allowed):
            raise ValueError(f"disallowed expression: {s!r}")
    return int(eval(compile(node, "<total_batch_size_str>", "eval")))  # noqa: S307


def configs_from_args(args: argparse.Namespace,
                      model_defaults: LLMConfig | None = None,
                      train_defaults: TrainConfig | None = None,
                      ) -> tuple[LLMConfig, TrainConfig]:
    """Route parsed flags onto the owning dataclass (reference
    single-gpu/train.py:183-197): strings lowercased except
    `non_linearity` and paths; act_recomp is copied into the model config
    (reference train.py:189-190)."""
    model_defaults = model_defaults or LLMConfig()
    train_defaults = train_defaults or TrainConfig()
    model_fields = {f.name for f in dataclasses.fields(LLMConfig)}
    train_fields = {f.name for f in dataclasses.fields(TrainConfig)}
    no_lower = {"non_linearity", "file_name", "data_dir", "profile_dir"}

    m_kw, t_kw = {}, {}
    for key, value in vars(args).items():
        if key == "total_batch_size_str":
            t_kw["total_batch_size"] = _safe_int_expr(value)
            continue
        if isinstance(value, str) and key not in no_lower:
            value = value.lower().strip()
        if key in train_fields:
            t_kw[key] = value
        if key in model_fields:
            m_kw[key] = value
    # act_recomp lives in both configs; train's flag wins (reference
    # train.py:189-190 links them).
    if "act_recomp" in t_kw:
        m_kw["act_recomp"] = t_kw["act_recomp"]
    model = dataclasses.replace(model_defaults, **m_kw)
    train = dataclasses.replace(train_defaults, **t_kw)
    return model, train
