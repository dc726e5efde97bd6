"""Sampling CLI: `python -m distributed_pytorch_tpu.sample --ckpt <dir>`.

The reference ships `LLM.generate` (single-gpu/model.py:700-747) but no
trainer or script ever calls it (SURVEY.md §3.4 "capability exists only as
API surface"); this CLI closes that gap: load a checkpoint written by the
trainer (`--save_model` / `--ckpt_interval`), tokenize a prompt, decode.

A prompt of comma-separated token ids is taken as ids and answered in
ids. A TEXT prompt is tokenized with tiktoken's GPT-2 BPE (the prepare
scripts' vocabulary), which is resolved only then: `get_encoding` fetches
its vocabulary over the network on first use, and neither this CLI nor
the server may wait on (or die of) that attempt on a machine without one
when the caller sends ids.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import os
import time

import jax
import jax.numpy as jnp


def _encoder():
    try:
        import tiktoken
        return tiktoken.get_encoding("gpt2")
    except Exception:
        return None


class TokenizerUnavailable(RuntimeError):
    """A text prompt arrived and the GPT-2 BPE files cannot be had."""


class LazyEncoder:
    """The GPT-2 BPE, resolved when a text prompt FIRST needs it — never
    at start-up (module docstring). `decode` answers only once a text
    prompt has resolved the encoder: clients that send ids get ids."""

    def __init__(self):
        self._enc = None
        self._tried = False

    def encode(self, text: str, **kw) -> list:
        if not self._tried:
            self._tried = True
            t0 = time.perf_counter()
            self._enc = _encoder()
            print(f"tokenizer: gpt2 BPE "
                  f"{'ready' if self._enc is not None else 'UNAVAILABLE'} "
                  f"after {time.perf_counter() - t0:.2f}s (resolved on the "
                  "first text prompt)")
        if self._enc is None:
            raise TokenizerUnavailable(
                "no tokenizer available (tiktoken could not load the gpt2 "
                "vocabulary — no network?); send the prompt as token ids")
        return self._enc.encode(text, **kw)

    def decode(self, toks: list) -> str:
        if self._enc is None:
            raise TokenizerUnavailable("no text prompt has resolved the "
                                       "tokenizer yet")
        return self._enc.decode(toks)


def parse_ids(prompt: str):
    """`prompt` as a list of token ids when it is comma-separated
    integers, else None (it is text)."""
    parts = [t.strip() for t in prompt.split(",") if t.strip()]
    if parts and all(t.isdigit() for t in parts):
        return [int(t) for t in parts]
    return None


def load_for_inference(ckpt: str, *, shard: bool = False, log=print):
    """Restore a trainer checkpoint for decoding; shared by this CLI and
    the serving front-end (`python -m distributed_pytorch_tpu.serve`).

    Returns `(model, variables, model_cfg, train_cfg, mesh, step,
    weights_version)` — `mesh` is None unless `shard` asked for (and the
    device count allows) a sharded restore in the checkpoint's
    training-recipe layout; `weights_version` is the step dir's identity
    (`step_N-<manifest digest prefix>`, checkpoint.weights_version; None
    for manifest-less dirs) that the serving front-end surfaces on
    /metrics and every completion payload. pp checkpoints are unstacked
    into the loop model (pipeline doesn't support KV caches); optimizer
    moments are never materialized."""
    from distributed_pytorch_tpu.train import checkpoint as ckpt_mod
    from distributed_pytorch_tpu.train.state import (build_model,
                                                     init_train_state,
                                                     make_optimizer)

    path = ckpt
    if not os.path.exists(os.path.join(path, "config.json")):
        last = ckpt_mod.latest_step_dir(path)
        assert last is not None, f"no checkpoint found under {path}"
        path = last
    model_cfg, train_cfg, step = ckpt_mod.load_configs(path)
    weights_version = ckpt_mod.weights_version(path)
    log(f"loaded config from {path} (step {step}): "
        f"{model_cfg.n_layer}L/{model_cfg.n_embd}d {model_cfg.attn}"
        + (f" [{weights_version}]" if weights_version else ""))

    # Shapes only (jax.eval_shape): no concrete init of params or AdamW
    # moments just to learn the checkpoint's structure; restore skips the
    # optimizer moments entirely (placeholder leaves).
    model = build_model(model_cfg, train_cfg)
    tx = make_optimizer(train_cfg)
    abstract = jax.eval_shape(
        lambda r: init_train_state(r, model, model_cfg, tx,
                                   batch_size=train_cfg.batch_size),
        jax.random.PRNGKey(0))
    shardings = None
    mesh = None
    if shard and len(jax.devices()) > 1:
        from distributed_pytorch_tpu.parallel.mesh import mesh_for
        from distributed_pytorch_tpu.train.state import (state_shardings,
                                                         state_spec_tree)
        mesh = mesh_for(train_cfg.parallelism, tp_size=train_cfg.tp_size,
                        ep_size=train_cfg.ep_size, sp_size=train_cfg.sp_size,
                        pp_size=train_cfg.pp_size)
        spec_tree = state_spec_tree(abstract, train_cfg.parallelism, mesh)
        shardings = state_shardings(abstract, train_cfg.parallelism, mesh)
        from jax.sharding import PartitionSpec as P
        n_sharded = sum(
            1 for s in jax.tree_util.tree_leaves(
                spec_tree.params, is_leaf=lambda x: isinstance(x, P))
            if any(a is not None for a in s))
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if n_sharded:
            log(f"sharded restore: mesh {sizes}, {n_sharded} param "
                f"leaves sharded ({train_cfg.parallelism} layout)")
        else:
            log(f"--shard: recipe {train_cfg.parallelism!r} replicates "
                "all params — restore is NOT memory-sharded (use an "
                "fsdp/tp/pp checkpoint for models larger than one "
                "device)")
    state = ckpt_mod.restore_for_inference(path, abstract, shardings)
    params = state.params
    if model_cfg.pp_stages > 1:
        # pipeline checkpoints store the blocks stacked on a layer axis;
        # decoding runs the loop model, so unstack and rebuild
        # (models/pipeline.py — pp doesn't support KV caches itself)
        from distributed_pytorch_tpu.models.pipeline import \
            unstack_block_params
        params = unstack_block_params(params, model_cfg.n_layer)
        if state.moe_state:
            # the aux-free bias is layer-stacked under pp too
            state = dataclasses.replace(
                state, moe_state=unstack_block_params(state.moe_state,
                                                      model_cfg.n_layer))
        model_cfg = dataclasses.replace(model_cfg, pp_stages=1,
                                        pp_microbatches=0)
        model = build_model(model_cfg, train_cfg)
        log("pp checkpoint: unstacked block params for decoding")
    variables = {"params": params}
    if state.moe_state:
        variables["moe_state"] = state.moe_state
    return (model, variables, model_cfg, train_cfg, mesh, step,
            weights_version)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description="Sample from a trained checkpoint")
    p.add_argument("--ckpt", type=str, required=True,
                   help="checkpoint dir (checkpoints/<name>/step_N or the "
                        "<name> root, in which case the newest step is used)")
    p.add_argument("--prompt", type=str, default="\n",
                   help="text prompt, or comma-separated token ids (then "
                        "the output is ids too and no tokenizer is "
                        "touched)")
    p.add_argument("--max_new_tokens", type=int, default=200)
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--top_k", type=int, default=50)
    p.add_argument("--num_samples", type=int, default=1)
    p.add_argument("--seed", type=int, default=1729)
    p.add_argument("--shard", action="store_true",
                   help="restore the checkpoint sharded over all local "
                        "devices using its training recipe's layout — for "
                        "models larger than one device's memory")
    p.add_argument("--cache-dtype", "--cache_dtype", dest="cache_dtype",
                   default="", choices=["", "int8", "bfloat16", "float32"],
                   help="KV-cache dtype for decoding; 'int8' quantizes the "
                        "cache on the ring write (ops/quant.py) and routes "
                        "decoding through the DecodeEngine")
    p.add_argument("--quant-weights", "--quant_weights",
                   dest="quant_weights", action="store_true",
                   help="weight-only int8 decode: params quantized once, "
                        "decode matmuls read int8 codes + per-channel "
                        "scales (prefill stays bf16); routes decoding "
                        "through the DecodeEngine")
    p.add_argument("--prefill-chunk", "--prefill_chunk",
                   dest="prefill_chunk", type=int, default=0,
                   help="Sarathi-style chunked prefill fused into the "
                        "decode step (engine/decode.py): <=N prompt "
                        "tokens per fused step; routes decoding through "
                        "the DecodeEngine. 0 = legacy one-shot wave "
                        "prefill (the baseline)")
    args = p.parse_args(argv)

    from distributed_pytorch_tpu.config import enable_compile_cache
    from distributed_pytorch_tpu.models.generate import make_generate_fn
    enable_compile_cache()

    from distributed_pytorch_tpu.obs.paths import device_record
    device = device_record()
    print(f"backend {device['platform']}: {device['count']} device(s) of "
          f"kind {device['kind']!r}")
    model, variables, model_cfg, train_cfg, mesh, _, _ = load_for_inference(
        args.ckpt, shard=args.shard)

    enc = None
    ids = parse_ids(args.prompt)
    if ids is None:
        enc = LazyEncoder()
        ids = enc.encode(args.prompt, allowed_special="all") or [0]
    ids = ids[-model_cfg.block_size:]
    T0 = len(ids)
    # Bucket the prompt length to the next power of two (right-padded;
    # decode starts from the TRUE length via prompt_len) so repeated
    # prompts reuse one trace per bucket instead of retracing per exact
    # (B, T0) — the jit cache key is the padded shape.
    bucket = 8
    while bucket < T0:
        bucket *= 2
    bucket = min(bucket, model_cfg.block_size)
    prompt = jnp.asarray(ids + [0] * (bucket - T0), jnp.int32)[None]

    n_new = args.num_samples * args.max_new_tokens
    if args.cache_dtype or args.quant_weights or args.prefill_chunk:
        # quantized serving / chunked-prefill knobs route through the
        # DecodeEngine (the generate scan has neither path): one slot per
        # sample, continuous batching degenerate to a single admit wave
        from distributed_pytorch_tpu.engine import DecodeEngine
        eng = DecodeEngine(model, variables, n_slots=args.num_samples,
                           cache_dtype=args.cache_dtype or None,
                           quantize_weights=args.quant_weights,
                           temperature=args.temperature, top_k=args.top_k,
                           rng=jax.random.PRNGKey(args.seed),
                           mesh=mesh,
                           recipe=train_cfg.parallelism if mesh is not None
                           else "single",
                           prefill_chunk=args.prefill_chunk)
        t0 = time.perf_counter()
        outs = eng.run([ids] * args.num_samples, args.max_new_tokens)
        dt = time.perf_counter() - t0
        print(f"decode: {n_new} tokens in {dt:.2f}s "
              f"({n_new / dt:.1f} tok/s, incl. compile on first call; "
              f"engine, cache={jnp.dtype(eng.cache_dtype).name} "
              f"quant_w={eng.weights_quantized} "
              f"prefill_chunk={eng.prefill_chunk or 'wave'})")
        for toks in outs:
            print("-" * 40)
            print(enc.decode(toks) if enc is not None else toks)
        return

    gen = make_generate_fn(model, args.max_new_tokens,
                           temperature=args.temperature, top_k=args.top_k)
    rng = jax.random.PRNGKey(args.seed)
    from distributed_pytorch_tpu.parallel import context
    with (context.use_mesh(mesh) if mesh is not None
          else contextlib.nullcontext()):
        # all samples decode as ONE batched call (one compile, one scan);
        # jax.random.categorical draws independent noise per batch row
        prompts = jnp.tile(prompt, (args.num_samples, 1))
        lens = jnp.full((args.num_samples,), T0, jnp.int32)
        t0 = time.perf_counter()
        out = jax.device_get(gen(variables, prompts, rng, lens))
        dt = time.perf_counter() - t0
    print(f"decode: {n_new} tokens in {dt:.2f}s "
          f"({n_new / dt:.1f} tok/s, incl. compile on first call; "
          f"prompt bucket {T0} -> {bucket}; "
          f"cache={jnp.dtype(model.compute_dtype).name} quant_w=False)")
    for toks in out.tolist():
        # splice out the pad tail: [prompt, pad, generated] -> real tokens
        toks = toks[:T0] + toks[bucket:]
        print("-" * 40)
        print(enc.decode(toks) if enc is not None else toks)


if __name__ == "__main__":
    main()
