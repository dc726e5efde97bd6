"""Runner of kind `train`: the trainer users run, `train.loop.train()`,
bounded to the measured window from outside.

`train()` calls its `log` argument at every sync boundary whose iteration
is a multiple of `log_interval` (the "iter N | loss ..." line, printed
right after the `device_get` that fences the window). The runner stamps its
own clock there. The first boundary closes the compile window, one more
window is dropped as warm-up, and the measured window runs from that stamp
to the first boundary at or after `--seconds`: whole log windows only, so
the rate is all the tokens of the window over all its time. The run is
then ended by raising from the callback; nothing of the program is edited
and no checkpoint is written (the SIGTERM path would write 18 GB at 1.5B).

The trainer's telemetry endpoint (`metrics_port=0`, its own public HTTP
surface) hands over the step timeline and the retrace gauge; the runner
reads them at the window's edges, outside the measured time.
"""

from __future__ import annotations

import gc
import json
import math
import os
import re
import urllib.request

import jax
import jax.numpy as jnp

from benchmark.lib import compiles, harness, peaks, reference, stats, synth
from benchmark.lib import flops as flops_lib
from benchmark.lib import compared, trace_reduce

# imported with the runner, which run.py resolves BEFORE it touches the
# chip: the trainer's import chain (orbax -> google.cloud.logging) took 42 s
# after the TPU runtime was up and 16 s before (my chip runs, PR 24)
from distributed_pytorch_tpu.config import LLMConfig, TrainConfig
from distributed_pytorch_tpu.models.gpt import LLM
from distributed_pytorch_tpu.train.loop import train

ITER_LINE = re.compile(r"^iter\s+(\d+)\s+\|\s+loss\s+(\S+)")
PORT_LINE = re.compile(r"telemetry: http://127\.0\.0\.1:(\d+)/metrics")
TRACES_GAUGE = re.compile(r"^train_step_traces_total\s+(\S+)", re.M)

# The system (bf16 compute, the program's attention and loss paths) against
# the float32 reference on the same seeded weights and tokens, at the cell's
# own sequence length so `auto` takes the path it trains on.
#
# Logits, per position, `reference.logits_error`: bf16 compute through the
# whole depth moves them by 1.21-1.29% of their rms at the worst position
# (my chip runs, PR 24, seven seeds; 1.26% on the CPU). On the same scale,
# at the gpt2 configuration (benchmark/tests/test_reference_check.py):
# attention's weights rounded to scaled fp8 5.8%, all weights 9.7%, the last
# layer's attention zeroed 20%, the mask one off 76%, every layer's
# attention zeroed 134%. The tolerance is 2.4 times the bf16 error and half
# the mildest of those.
#
# Loss: the scalar through the program's loss path (chunked CE) against the
# reference's. It says little about the layers before it (zeroing attention
# moves it by 1e-2 at initialisation, fp8 weights by 3e-4): it gates the
# loss path alone. Measured 3.2e-5 to 2.2e-4 (my chip runs, PR 24).
LOGIT_ERROR_TOLERANCE = 0.03
LOSS_TOLERANCE = 2e-3


class _WindowOver(Exception):
    """Raised from the trainer's log callback to end the run."""


def _get(port: int, path: str) -> bytes:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=30) as r:
        return r.read()


class _Boundaries:
    """The log callback: stamps boundaries, opens and closes the window,
    brackets the traced slice, ends the run."""

    def __init__(self, ctx, warmup_windows: int, trace_windows: int,
                 trace_dir: str):
        self.say = ctx["say"]
        self.seconds = ctx["seconds"]
        self.trace = ctx["trace"]
        self.warmup_windows = warmup_windows
        self.trace_windows = trace_windows
        self.trace_dir = trace_dir
        self.port = None
        self.stamps: list = []           # (it, t, loss) per boundary
        self.i_open = None               # index into stamps
        self.i_close = None
        self.i_trace0 = None
        self.traces_at_open = None
        self.traces_at_close = None
        self.memory_peak = None
        self.timeline: list = []
        self.trace_summary = None

    def _traces(self):
        if self.port is None:
            return None
        m = TRACES_GAUGE.search(_get(self.port, "/metrics").decode())
        return float(m.group(1)) if m else None

    def __call__(self, s: str) -> None:
        t = stats.now()
        print(f"[+{t - stats.T_PROCESS_START:7.2f}s] {s}", flush=True)
        m = PORT_LINE.search(s)
        if m:
            self.port = int(m.group(1))
            return
        m = ITER_LINE.match(s)
        if not m:
            return
        self.stamps.append((int(m.group(1)), t, float(m.group(2))))
        i = len(self.stamps) - 1
        if self.i_open is None:
            if i == self.warmup_windows:
                self.i_open = i
                self.traces_at_open = self._traces()
                self.say(f"window opens at iter {self.stamps[i][0]}")
            return
        if self.i_close is None:
            if t - self.stamps[self.i_open][1] < self.seconds:
                return
            self.i_close = i
            self.traces_at_close = self._traces()
            self.memory_peak = peaks.memory_peak_bytes()
            self.say(f"window closes at iter {self.stamps[i][0]}")
            if self.trace:
                trace_reduce.start_trace(self.trace_dir)
                self.i_trace0 = i
                return
            self._finish()
        if self.trace and i - self.i_trace0 >= self.trace_windows:
            jax.profiler.stop_trace()
            self.say("traced slice written")
            self._finish()

    def _finish(self):
        if self.port is not None:
            body = json.loads(_get(self.port, "/debug/timeline?n=4096"))
            self.timeline = body["entries"]
        raise _WindowOver()


def _program_configs(ctx, data_dir: str):
    llm = ctx["config"]["llm_config"]
    model_cfg = LLMConfig(**llm)
    kw = dict(ctx["traffic"]["train_config"])
    kw.update(dataset="synthetic", data_dir=data_dir, eval=False,
              save_model=False, save_stats=False,
              seed=harness.seed31(ctx["seed"]),
              file_name=f"bench_{ctx['cell']['name']}", metrics_port=0)
    return model_cfg, TrainConfig(**kw), llm


def compare_to_reference(model, variables, reference_params, llm: dict,
                         x, y) -> dict:
    """The program's model on `variables` against the plain reference on
    `reference_params` (the same tree in a run; the tests hand over a
    spoilt one to show what the comparison sees)."""
    sys_logits, sys_loss = jax.jit(
        lambda v, a, b: model.apply(v, a, b)[:2])(variables, x, y)
    ref_logits = reference.forward_logits(reference_params, llm, x)
    ref_loss = float(reference.cross_entropy(ref_logits, y))
    err = {k: float(v) for k, v in
           reference.logits_error(sys_logits, ref_logits).items()}
    loss_delta = abs(float(sys_loss) - ref_loss)
    return {"logit_error_worst": err["worst"],
            "logit_error_median": err["median"],
            "system_loss": float(sys_loss), "reference_loss": ref_loss,
            "loss_delta": loss_delta,
            "ok": err["worst"] <= LOGIT_ERROR_TOLERANCE
            and loss_delta <= LOSS_TOLERANCE}


def reference_check(ctx, model_cfg, train_cfg, llm: dict) -> dict:
    """(a) of `correct`, on a seeded sample of `reference_sample` tokens.
    Weights are made on the device in one jitted call and exist once."""
    B, T = ctx["traffic"]["reference_sample"]
    model = LLM(model_cfg, compute_dtype=jnp.dtype(train_cfg.compute_dtype),
                attn_impl=train_cfg.attn_impl)
    key = jax.random.PRNGKey(harness.seed31(ctx["seed"]) + 1)
    dummy = jnp.zeros((1, T), jnp.int32)
    variables = jax.jit(model.init)({"params": key, "dropout": key},
                                    dummy, dummy)
    toks = synth.sample_tokens(ctx["seed"] + 2, (B, T + 1),
                               model_cfg.vocab_size)
    return compare_to_reference(
        model, variables, variables["params"], llm,
        jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:]))


def run(ctx: dict) -> dict:
    say = ctx["say"]
    t = ctx["traffic"]
    work = ctx["work_dir"]
    os.chdir(work)                 # the trainer writes runs/ beside itself
    data_dir = os.path.join(work, "data")
    model_cfg, train_cfg, llm = _program_configs(ctx, data_dir)
    for name, n, salt in (("train.bin", t["synthetic_tokens"], 0),
                          ("val.bin", 2 ** 17, 1)):
        synth.write_token_file(
            os.path.join(data_dir, "synthetic", name), n,
            model_cfg.vocab_size, harness.seed31(ctx["seed"]) + salt)
    say("token files written")
    trace_dir = os.path.join(work, "trace")

    compile_log = compiles.CompileLog()
    cb = _Boundaries(ctx, t["warmup_windows"], t["trace_windows"], trace_dir)
    try:
        train(model_cfg, train_cfg, log=cb)
        raise RuntimeError("the trainer ended before the window closed: "
                           "raise max_iters in the traffic file")
    except _WindowOver:
        pass
    gc.collect()

    it0, t0, _ = cb.stamps[cb.i_open]
    it1, t1, _ = cb.stamps[cb.i_close]
    tokens_per_step = train_cfg.total_batch_size
    chips = ctx["chips"]
    rate = (it1 - it0) * tokens_per_step / (t1 - t0) / chips
    setup_s = t0 - stats.T_PROCESS_START
    T = model_cfg.block_size
    say(f"window: iters {it0}..{it1} ({it1 - it0} steps) in {t1 - t0:.3f}s "
        f"-> {rate:.1f} tokens/s/chip, MFU "
        f"{flops_lib.mfu(llm, T, rate, ctx['peaks']['bf16_flops']):.4f} "
        f"(model FLOPs, no recomputation counted); setup {setup_s:.2f}s")

    steps = [e for e in cb.timeline
             if isinstance(e.get("it"), int) and "loss" in e
             and "event" not in e]
    in_window = [e for e in steps if it0 < e["it"] <= it1]
    losses = [e["loss"] for e in in_window]
    if not losses:                      # no endpoint: boundary lines only
        losses = [l for it, _, l in cb.stamps if it0 < it <= it1]
    bad = [l for l in losses if not math.isfinite(l)]
    first_loss = cb.stamps[0][2]
    last_it0 = cb.stamps[cb.i_close - 1][0]
    last_window = [e["loss"] for e in in_window if e["it"] > last_it0] \
        or [cb.stamps[cb.i_close][2]]
    last_mean = sum(last_window) / len(last_window)
    falling = last_mean < first_loss
    say(f"losses: first step {first_loss:.4f}, last window mean "
        f"{last_mean:.4f}, {len(bad)} non-finite of {len(losses)}")

    ref = reference_check(ctx, model_cfg, train_cfg, llm)
    say(f"reference: logits off by {ref['logit_error_worst']:.5f} of their "
        f"rms at the worst position, {ref['logit_error_median']:.5f} at the "
        f"median one (tolerance {LOGIT_ERROR_TOLERANCE}); loss "
        f"{ref['system_loss']:.5f} vs float32 reference "
        f"{ref['reference_loss']:.5f} (delta {ref['loss_delta']:.2e}, "
        f"tolerance {LOSS_TOLERANCE})")

    late = compile_log.between(t0, t1)
    retraces = (cb.traces_at_close - cb.traces_at_open
                if None not in (cb.traces_at_open, cb.traces_at_close) else 0)
    say(f"compiles: {len(compile_log.events)} programs, "
        f"{compile_log.total_seconds(t0):.1f}s of set-up; inside the window "
        f"{[e[1] for e in late]}, retraces {retraces}")
    obs = {"timeline": in_window, "peaks": ctx["peaks"],
           "counters": {"compiles_in_window": max(len(late), retraces)}}
    if cb.memory_peak:
        obs["counters"]["peak_hbm_gib"] = cb.memory_peak / 2 ** 30
    if ctx["trace"]:
        obs["trace"] = trace_reduce.reduce_trace_dir(
            trace_dir, chips, cb.stamps[-1][0] - cb.stamps[cb.i_trace0][0],
            say)

    return {"correct": bool(ref["ok"] and not bad and falling),
            "compared": [
                compared.entry("logit_error_worst", ref["logit_error_worst"],
                               LOGIT_ERROR_TOLERANCE, "at_most"),
                compared.entry("loss_delta", ref["loss_delta"],
                               LOSS_TOLERANCE, "at_most"),
                compared.entry("losses_not_finite", len(bad), 0, "at_most"),
                # the last window's mean loss under the first step's
                compared.entry("loss_fall", first_loss - last_mean, 0.0,
                               "at_least")],
            "attempted": len(losses), "failed": len(bad),
            "end_to_end": {"train_tokens_per_s": rate, "setup_s": setup_s},
            "observations": obs,
            "memory_peak_bytes": cb.memory_peak}
