"""`compile_check.py` for a cell of runner kind `serve_closed_hybrid`: the
patterned model's init and its two step programs, compiled at the cell's
real size for a DESCRIBED v5e (nothing attached), with the compiler's
memory accounting. `compile_check.check_serve` builds a float32 tree and a
cache without per-slot state, so this kind brings its own.

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_check_hybrid.py \\
        --workload nemotron_h_serve_closed64

A script to run by hand. Nothing runs, so it says nothing about results or
times: it says that the programs compile, what they hold, and that no
program copies a pool, a state leaf or an expert stack (`--copies` lists
every copy over 32 MB)."""

from __future__ import annotations

import argparse
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_COPY = re.compile(r"= (\w+)\[([\d,]+)\]\S* copy\(")
_ITEM = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "f16": 2, "s8": 1, "u8": 1,
         "pred": 1}


def big_copies(text: str, floor: int = 32 * 2 ** 20) -> list:
    out = []
    for m in _COPY.finditer(text):
        n = _ITEM.get(m.group(1), 4)
        for d in m.group(2).split(","):
            n *= int(d)
        if n >= floor:
            out.append(f"{m.group(1)}[{m.group(2)}] {n / 2**20:.0f} MiB")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--copies", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    jax.config.update("jax_enable_compilation_cache", False)
    # code of the program that asks `jax.default_backend()` would take its
    # CPU branch here (Pallas kernels in interpret mode: while loops and
    # whole-operand copies that the chip never sees): steer it, in this
    # tool, to the branch the described chip takes
    jax.default_backend = lambda: "tpu"
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from benchmark.lib import harness
    from benchmark.tools.compile_check import _report
    from distributed_pytorch_tpu.config import LLMConfig
    from distributed_pytorch_tpu.engine import decode as dec
    from distributed_pytorch_tpu.models.gpt import LLM, init_paged_cache

    res = harness.resolve_cell(harness.load_benchmark(), args.workload)
    t, e = res["traffic"], res["traffic"]["engine"]
    cfg = LLMConfig(**res["config"]["llm_config"])
    dt = jnp.dtype(t["compute_dtype"])
    model = LLM(cfg, compute_dtype=dt, attn_impl=t["attn_impl"],
                param_dtype=dt)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    sds = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,  # noqa: E731
                                         sharding=chip)
    key = jax.random.PRNGKey(0)
    name = res["cell"]["name"]

    def report(what, compiled, t0):
        _report(f"{name} {what}", compiled, time.perf_counter() - t0)
        copies = big_copies(compiled.as_text())
        print(f"  copies of 32 MiB or more: {len(copies)}"
              + (f" {copies}" if args.copies or copies else ""), flush=True)

    init = jax.jit(lambda k: model.init({"params": k},
                                        jnp.zeros((1, 8), jnp.int32)))
    t0 = time.perf_counter()
    report("model.init", init.lower(sds(key)).compile(), t0)
    variables = jax.tree_util.tree_map(sds, jax.eval_shape(init, key))
    n_slots, bs = e["n_slots"], e["block_size"]
    max_blocks = e["max_len"] // bs
    n_blocks = n_slots * max_blocks + 1
    n_blocks += (-n_blocks) % 8
    width = max_blocks + e["prefill_chunk"] // bs
    caches = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: init_paged_cache(cfg, n_blocks, bs, dtype=dt,
                                 n_slots=n_slots)))
    size = lambda tree: sum(  # noqa: E731
        v.size * v.dtype.itemsize for v in jax.tree_util.tree_leaves(tree))
    print(f"engine arguments: weights {size(variables) / 2**30:.2f} GiB, "
          f"state and {n_blocks} blocks {size(caches) / 2**30:.2f} GiB",
          flush=True)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,  # noqa: E731
                                              sharding=chip)
    tok, pos, bt, scalar = i32(n_slots), i32(n_slots), i32(n_slots, width), \
        i32()
    live = jax.ShapeDtypeStruct((n_slots,), jnp.bool_, sharding=chip)
    rng = sds(key)
    sample = lambda logits, r: jnp.argmax(logits, axis=-1).astype(jnp.int32)  # noqa: E731
    step = jax.jit(dec.make_step_fn(model, sample), donate_argnums=(1,))
    t0 = time.perf_counter()
    report("engine.step", step.lower(variables, caches, tok, pos, live, bt,
                                     rng, scalar, None).compile(), t0)
    fused = jax.jit(dec.make_fused_step_fn(model, sample, n_slots, width),
                    donate_argnums=(1,))
    t0 = time.perf_counter()
    report("engine.fused_step", fused.lower(
        variables, caches, tok, pos, live, bt, rng, scalar, None,
        i32(1, e["prefill_chunk"]), scalar, scalar, i32(1),
        jax.ShapeDtypeStruct((), jnp.bool_, sharding=chip)).compile(), t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
