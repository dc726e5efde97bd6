"""What the engine counts, in one place: the lifetime totals, the moments
that add to them, the readings derived from them, and the ONE table of what
an operator sees (`READINGS`: `/metrics` and `/debug/timeline` walk it;
serve/scheduler.py and serve/server.py hold no counter's name).

`DecodeEngine` keeps one `EngineCounts` as `counts` and forwards every name
it lacks to it, so `engine.expert_calls` reads as it always did. A program
is booked ONCE, when it has drained (`drained`), and its share comes back as
the fields of its flight record. What happens outside a drain keeps its
moment (`admitted`, `planned`, `retired`, `overran`). No statement outside
this file adds to a total. A new counter is a total in `__init__`, a line
where it is booked and, for an operator to see it, an entry in `READINGS`."""

from typing import Any, NamedTuple, Optional

import jax
import numpy as np

#: Why a sequence left its slot — the serving layer routes on these.
#: 'preempted' carries partial output that callers REQUEUE, never drop.
RETIRE_REASONS = ("eos", "budget", "cache_full", "cancelled", "preempted")

#: what a call carried: a chunk's rows (alone or with the decode rows) | those
KINDS = ("chunk", "decode")


def _ratio(part, whole) -> float:
    return part / whole if whole else 0.0


def _share(part: str, whole: str, doc: Optional[str] = None) -> property:
    """A lifetime ratio of two totals, 0 before its first event."""
    return property(
        lambda c: _ratio(getattr(c, part), getattr(c, whole)), doc=doc)


class EngineCounts:
    """The lifetime totals of one engine, plain attributes all, from the
    model's layer counts. Rows, bytes and steps are by the host's planned
    lengths, a layer's call each; `*_by` totals are by `KINDS`."""

    def __init__(self, cfg, caches, n_slots: int, prefill_chunk: int):
        self._cfg = cfg
        self._prefill_chunk = prefill_chunk
        self.n_programs = 0           # dispatched (the engine's `n_steps`)
        self.n_admitted = 0
        self.retire_counts = dict.fromkeys(RETIRE_REASONS, 0)
        self.prompt_tokens = 0        # prompt tokens across admissions
        self.prefix_hit_tokens = 0    # of those, served from cached blocks
        self.prefilled_tokens = 0     # suffix tokens actually prefilled
        self.spec_drafted_tokens = 0  # drafter proposals sent to verify
        self.spec_accepted_tokens = 0  # of those, accepted by the target
        self.emitted_tokens = 0       # tokens emitted across all steps
        # computed for an occupant that had left by the drain: an `eos`
        # seen one program late, a cancel while its program ran
        self.overrun_tokens = 0
        self.overlapped_programs = 0  # dispatched behind a running one
        self.drain_reasons: dict[str, int] = {}  # the others, by why not
        self.chunk_programs = 0       # drained programs that carried a chunk
        self.chunked_prompts = 0      # prompts whose last chunk has run
        # of those programs, the ones whose expert layers made ONE call
        # over the chunk's rows and the decode rows (`make_fused_step_fn`)
        self.merged_programs = 0
        # of the decode attention calls' walk, a grid step a sequence
        self.decode_live_tiles = 0
        self.decode_live_steps = 0
        # key rows the attention calls had to read, in the layers that
        # keep the whole history (latent layers' among them, and apart in
        # rows of their own kind) and in the window layers; (query, key)
        # pairs the chunk calls' masks let through, a chunk's REAL rows alone
        self.n_full = cfg.layers_keeping("pools")
        self.n_window = cfg.layers_keeping("window")
        self.n_latent = cfg.layer_pattern.count("L")
        # planned where the layers differ in what they keep (window layers,
        # or two kinds in one: 'P') or a kernel's roofline reads them ('L';
        # the pools beside 'G' layers, whose chunks reach 32k keys)
        self.plans_kv_rows = bool(
            self.n_window or self.n_latent
            or cfg.layer_pattern.count("G")) or any(
            len(keeps) > 1 for keeps in cfg.layer_keeps)
        self.kv_rows_read_full_by = dict.fromkeys(KINDS, 0)
        self.kv_rows_read_window_by = dict.fromkeys(KINDS, 0)
        self.latent_rows_read_by = dict.fromkeys(KINDS, 0)
        self.window_rows_saved = 0
        self.chunk_attn_pairs_by = {"full": 0, "window": 0}
        # a slot's recurrent state began anew (an admission's first chunk)
        self.state_resets = 0
        # float32 state the state-space layers' calls read and wrote back:
        # the planned decoding slots (a chunk: its one) x a slot's, in and out
        self.state_bytes_slot = sum(
            leaf.size * leaf.dtype.itemsize // n_slots
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                caches)[0] if getattr(path[-1], "key", None) == "ssm")
        self.ssm_state_bytes_by = dict.fromkeys(KINDS, 0)
        # linear-attention layers ('K', 'G'): the planned decoding slots x
        # layers (a state read and written once each) | a chunk's real rows x
        # layers
        self.n_kda = sum(cfg.layer_pattern.count(kind) for kind in "KG")
        self.kda_slot_steps_by = dict.fromkeys(KINDS, 0)
        # expert layers ('E'): calls, held experts hit, real rows'
        # assignments to held and to absent experts; of softmax-routed
        # programs alone: the kernels' tiles beyond a hit expert's first,
        # the calls that had one, the routing weights that fell on the held
        self.n_expert = cfg.layer_pattern.count("E")
        self.expert_calls = 0
        self.experts_hit = 0
        self.held_assignments = 0
        self.absent_assignments = 0
        self.expert_calls_by = dict.fromkeys(KINDS, 0)
        self.expert_second_tiles_by = dict.fromkeys(KINDS, 0)
        self.expert_second_tile_calls_by = dict.fromkeys(KINDS, 0)
        self.held_gate_sum = 0.0
        n_held = (cfg.experts_held or (0, cfg.n_routed))[1]
        self.expert_tokens = np.zeros((n_held,), np.int64)

    # -- the moments that add to the totals -----------------------------

    def admitted(self, prompt_tokens: int, prefix_hit_tokens: int, *,
                 wave_prefilled: Optional[int] = None,
                 stats: Optional[list] = None) -> None:
        """An admission. A wave engine's ran a prefill program of its own
        over `wave_prefilled` ids (`stats`: its FETCHED expert counts); a
        chunked one's chunks are booked as they drain."""
        self.n_admitted += 1
        self.prompt_tokens += prompt_tokens
        self.prefix_hit_tokens += prefix_hit_tokens
        if wave_prefilled is not None:
            self.prefilled_tokens += wave_prefilled
            self.state_resets += int(self._cfg.recurrent)
            self.ssm_state_bytes_by["chunk"] += 2 * self.state_bytes_slot
            self.kda_slot_steps_by["chunk"] += wave_prefilled * self.n_kda
            self.count_experts(stats, ("chunk",))

    def planned(self, n_programs: int, reason: Optional[str]) -> None:
        """A program is planned, the engine's `n_programs`-th: behind a
        running one (`reason` None) or for why not."""
        self.n_programs = n_programs
        if reason is None:
            self.overlapped_programs += 1
        else:
            self.drain_reasons[reason] = \
                self.drain_reasons.get(reason, 0) + 1

    def retired(self, reason: str) -> None:
        self.retire_counts[reason] += 1

    def overran(self, tokens: int) -> None:
        """Tokens of a program nobody drains: everyone it ran for left."""
        self.overrun_tokens += tokens

    def count_experts(self, stats: Optional[list],
                      kinds: tuple = ()) -> tuple[int, int, int]:
        """Fold one program's FETCHED routing counts (host arrays) into the
        totals; (experts hit, absent assignments, second tiles) of the
        program. A layer's leaves hold a row a CALL of its kernels: one a
        program, or two of a fused step that ran the model twice; `kinds` =
        what each carried, in the program's order. A layer that carries its
        kernels' tile count out (`tiles`) has the tiles beyond one an expert
        hit counted."""
        hit = absent = second = 0
        for layer in stats or ():
            tokens = layer["tokens"]                        # (calls, held)
            self.expert_calls += tokens.shape[0]
            self.expert_tokens += tokens.sum(axis=0)
            self.held_assignments += int(tokens.sum())
            hits = (tokens > 0).sum(axis=1)                 # (calls,)
            hit += int(hits.sum())
            absent += int(layer["absent"].sum())
            self.held_gate_sum += float(layer["held_gate"].sum()) \
                if "held_gate" in layer else 0.0
            if "tiles" not in layer:
                continue
            for what, extra in zip(kinds, layer["tiles"] - hits):
                self.expert_calls_by[what] += 1
                self.expert_second_tiles_by[what] += int(extra)
                self.expert_second_tile_calls_by[what] += int(extra > 0)
                second += int(extra)
        self.experts_hit += hit
        self.absent_assignments += absent
        return hit, absent, second

    def drained(self, prog, stats: Optional[list], *, emitted: int,
                overrun: int, drafted: int, accepted: int,
                retired: int) -> dict:
        """Book one drained program, once: what its plan wrote on `prog`
        (`_Program`), its FETCHED expert `stats` (None of a speculative one)
        and the drain's tallies. Returns its share, the fields of its flight
        record: a patterned model's and the planned rows only where any."""
        chunk, n_live = prog.chunk is not None, prog.n_live
        before = self.expert_calls
        hit, absent, second = self.count_experts(
            stats, ("chunk",) * chunk + ("decode",))
        calls = self.expert_calls - before
        self.state_resets += int(prog.state_reset)
        state_bytes = 2 * self.state_bytes_slot
        self.ssm_state_bytes_by["decode"] += state_bytes * n_live
        self.ssm_state_bytes_by["chunk"] += state_bytes * chunk
        self.kda_slot_steps_by["decode"] += n_live * self.n_kda
        live_steps = n_live if prog.spec is None else 0
        self.decode_live_tiles += prog.live_tiles
        self.decode_live_steps += live_steps
        kv_full = kv_window = 0
        for what, (whole, windowed) in (prog.kv_rows or {}).items():
            full, window = whole * self.n_full, windowed * self.n_window
            self.kv_rows_read_full_by[what] += full
            self.kv_rows_read_window_by[what] += window
            self.latent_rows_read_by[what] += whole * self.n_latent
            self.window_rows_saved += whole * self.n_window - window
            kv_full += full
            kv_window += window
        self.chunk_attn_pairs_by["full"] += prog.chunk_pairs[0] * self.n_full
        self.chunk_attn_pairs_by["window"] += \
            prog.chunk_pairs[1] * self.n_window
        prefill_tokens = 0
        if chunk:
            slot_c, sid_c, prefill_tokens, _ = prog.chunk
            self.kda_slot_steps_by["chunk"] += prefill_tokens * self.n_kda
            self.prefilled_tokens += prefill_tokens
            self.chunk_programs += 1
            self.merged_programs += int(
                bool(self.n_expert) and calls == self.n_expert)
            # only a prompt's last chunk makes its slot an occupant
            self.chunked_prompts += int(prog.occupants.get(slot_c) == sid_c)
        self.emitted_tokens += emitted
        self.overrun_tokens += overrun
        self.spec_drafted_tokens += drafted
        self.spec_accepted_tokens += accepted
        record = dict(
            n_live=n_live, prefill_tokens=prefill_tokens, emitted=emitted,
            retired=retired, preemptions=len(prog.preempted),
            drafted=drafted, accepted=accepted, overlapped=prog.overlapped,
            drain_reason=prog.drain_reason, overrun=overrun,
            decode_live_tiles=prog.live_tiles, decode_live_steps=live_steps)
        if self._cfg.layer_pattern:
            record.update(
                experts_hit=hit, absent_assignments=absent,
                expert_calls=calls, expert_second_tiles=second,
                state_reset=int(prog.state_reset),
                ssm_state_bytes=state_bytes * (n_live + chunk))
        if self.plans_kv_rows:
            record.update(kv_rows_read_full=kv_full,
                          kv_rows_read_window=kv_window)
        return record

    # -- the derived readings -------------------------------------------

    prefix_hit_rate = _share("prefix_hit_tokens", "prompt_tokens")
    accepted_token_rate = _share("spec_accepted_tokens", "spec_drafted_tokens")
    tokens_per_step = _share(
        "emitted_tokens", "n_programs",
        "Mean tokens emitted per step program: the speculative multiplier.")
    overlap_share = _share(
        "overlapped_programs", "n_programs",
        "Fraction of step programs dispatched behind a running one (the "
        "device had its next queued); the rest are in `drain_reasons`.")
    merged_program_share = _share(
        "merged_programs", "chunk_programs",
        "Share of the chunk-carrying programs that read the held experts "
        "once (one expert call a layer): 1.0 for a patterned model, 0 for "
        "a classic or a quantised engine, which run the model twice.")
    decode_tiles_per_grid_step = _share(
        "decode_live_tiles", "decode_live_steps",
        "Live cache tiles a grid step of the paged decode kernel held (a "
        "grid step is one sequence and walks all its live tiles): 1.0 = one "
        "a sequence, nothing for the kernel's fetches in flight to overlap.")
    chunk_programs_per_prompt = _share("chunk_programs", "chunked_prompts")

    @property
    def chunk_fill_share(self) -> float:
        """Share of the chunk rows the fused programs computed that held a
        real prompt id (the rest were pads, computed all the same): ids
        prefilled / (chunk-carrying programs x `prefill_chunk`)."""
        return _ratio(self.prefilled_tokens,
                      self.chunk_programs * self._prefill_chunk)

    @property
    def held_gate_share(self) -> float:
        """Mean share of a real row's routing weights that fell on experts
        held here: the part of an expert layer's routed output this chip
        computes. 0 where the router renormalises nothing to compare with
        (`route_sigmoid` carries no such count)."""
        rows = (self.held_assignments + self.absent_assignments) \
            / max(self._cfg.n_act_routed, 1)
        return _ratio(self.held_gate_sum, rows)

    # a call of an expert layer: the held experts it hit (the weight bytes
    # it must read) and its tiles beyond a hit expert's first; how evenly
    # the held experts are loaded; the routing that falls on another chip's
    experts_hit_per_call = _share("experts_hit", "expert_calls")
    expert_second_tiles_per_call = _share("expert_second_tiles",
                                          "expert_calls")

    @property
    def expert_tokens_max_over_mean(self) -> float:
        return (float(self.expert_tokens.max())
                / max(float(self.expert_tokens.mean()), 1.0)
                if self.expert_calls else 0.0)

    @property
    def absent_assignments_share(self) -> float:
        return self.absent_assignments / max(
            self.absent_assignments + self.held_assignments, 1)


# a total kept by kind of call reads as its sum under the name without `_by`
for _by in ("kv_rows_read_full_by", "kv_rows_read_window_by",
            "latent_rows_read_by", "ssm_state_bytes_by", "kda_slot_steps_by",
            "expert_second_tiles_by"):
    setattr(EngineCounts, _by[:-3], property(
        lambda self, _by=_by: sum(getattr(self, _by).values())))


class Reading(NamedTuple):
    """One thing an operator sees, read off the engine as `name`."""

    name: str
    metric: Optional[str] = None    # its gauge at /metrics, and the
    help: str = ""                  # gauge's help text
    timeline: bool = False          # /debug/timeline shows it, as `name`
    zero: Any = 0


#: what `/metrics` (serve/scheduler.py) and `/debug/timeline`
#: (serve/server.py) show of the counts: an entry here is all a new one takes
READINGS = (
    Reading("prefix_hit_rate", "serve_prefix_hit_rate",
            "lifetime fraction of prompt tokens served from cached blocks"),
    Reading("accepted_token_rate", "serve_spec_accepted_token_rate",
            "accepted/drafted fraction of speculative draft tokens"),
    Reading("tokens_per_step", "serve_engine_tokens_per_step",
            "mean tokens emitted per fused step (spec decode > 1)",
            zero=1.0),
    Reading("overlap_share", "serve_engine_overlap_share",
            "fraction of step programs dispatched behind a running one",
            timeline=True),
    Reading("chunk_fill_share", "serve_chunk_fill_share",
            "prompt ids prefilled / chunk rows the fused programs computed",
            timeline=True),
    Reading("decode_tiles_per_grid_step", "serve_decode_tiles_per_grid_step",
            "live cache tiles a grid step of the paged decode kernel held",
            timeline=True),
    Reading("merged_program_share", "serve_merged_program_share",
            "chunk-carrying step programs that read the held experts once",
            timeline=True),
    Reading("chunk_programs_per_prompt", "serve_chunk_programs_per_prompt",
            "chunk-carrying step programs per prompt chunked in",
            timeline=True),
    # a patterned model's layers; 0 for every other model
    Reading("experts_hit_per_call", "serve_experts_hit_per_call",
            "held experts that received a token, per expert-layer call"),
    Reading("expert_tokens_max_over_mean",
            "serve_expert_tokens_max_over_mean",
            "most-loaded held expert's tokens over the mean of the held"),
    Reading("absent_assignments_share",
            "serve_expert_absent_assignments_share",
            "share of routed assignments that fall on experts not held"),
    Reading("expert_second_tiles_per_call",
            "serve_expert_second_tiles_per_call",
            "expert-kernel tiles beyond a hit expert's first (each reads "
            "the expert's matrices again), per expert-layer call"),
    Reading("held_gate_share", "serve_expert_held_gate_share",
            "mean share of a token's routing weights that fell on held "
            "experts (routers that do not renormalise over the held)"),
    Reading("state_resets", "serve_state_resets_total",
            "recurrent state started anew (one per admission's first chunk)"),
    Reading("kv_rows_read_full", "serve_kv_rows_read_full_total",
            "key rows read by the attention calls of layers that "
            "keep the whole history", timeline=True),
    Reading("kv_rows_read_window", "serve_kv_rows_read_window_total",
            "key rows read by the window layers' attention calls",
            timeline=True),
    Reading("window_rows_saved", "serve_window_rows_saved_total",
            "key rows a whole history would have cost the window "
            "layers, less the rows they read", timeline=True),
    Reading("chunk_attn_pairs_by", timeline=True, zero={}),
    Reading("ssm_state_bytes", "serve_ssm_state_bytes_total",
            "float32 state the state-space layers' calls read and wrote "
            "back (slots x layers x a slot's state, in and out)"),
    Reading("ssm_state_bytes_by", timeline=True, zero={}),
    Reading("latent_rows_read", "serve_latent_rows_read_total",
            "live latent rows the latent-attention layers' decode "
            "and chunk calls had to read"),
    Reading("latent_rows_read_by", timeline=True, zero={}),
    Reading("kda_slot_steps", "serve_kda_slot_steps_total",
            "decoding slots' states and chunks' real rows the "
            "linear-attention layers' calls stepped, a layer each"),
    Reading("kda_slot_steps_by", timeline=True, zero={}),
)


def gauges(engine):
    """(name, live read, help text) of every gauge the table names. An
    engine that keeps no counts (a test's fake) reads the table's zero."""
    return [(r.metric, lambda r=r: getattr(engine, r.name, r.zero), r.help)
            for r in READINGS if r.metric]


def timeline(engine) -> dict:
    """The readings `/debug/timeline` shows beside the flight records."""
    return {r.name: getattr(engine, r.name, r.zero)
            for r in READINGS if r.timeline}
