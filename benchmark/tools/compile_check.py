"""Ask the TPU compiler before spending chip time: compile a cell's
programs at their real size for a DESCRIBED v5e:2x2 (nothing attached,
`jax.experimental.topologies`) and print the compiler's memory accounting.

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_check.py --workload <cell>

A script to run by hand, not a test and not part of a run: the topology is
described under `main()`, never at import. PERF.md's compiler numbers come
from it and are reproduced with it: the serving cell's argument and
temporary sizes as the line above stands; the fsdp step's 50 / 26 / 18 GB
(PERF.md section 6) once the mix of section 7's first row exists as a
traffic file and a four-chip cell, with `--set batch_size=<n>`. Nothing runs, so this says
nothing about results or times. Code of the program that asks
`jax.default_backend()` sees the CPU here (donation is off there, so
`argument + output` counts the state twice: read `temp` and `argument`).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _report(name: str, compiled, seconds: float) -> None:
    ma = compiled.memory_analysis()
    gib = 2 ** 30
    text = compiled.as_text()
    kernels = text.count("tpu_custom_call")
    print(f"{name}: compiled in {seconds:.1f}s | argument "
          f"{ma.argument_size_in_bytes / gib:.2f} GiB, output "
          f"{ma.output_size_in_bytes / gib:.2f} GiB, alias "
          f"{ma.alias_size_in_bytes / gib:.2f} GiB, temp "
          f"{ma.temp_size_in_bytes / gib:.2f} GiB | tpu_custom_call x"
          f"{kernels} | all-gather x{text.count('all-gather(') + text.count('all-gather-start(')}"
          f" reduce-scatter x{text.count('reduce-scatter(')}"
          f" all-reduce x{text.count('all-reduce(') + text.count('all-reduce-start(')}",
          flush=True)


def check_train(res, topo) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from distributed_pytorch_tpu.config import LLMConfig, TrainConfig
    from distributed_pytorch_tpu.parallel import sharding as shd
    from distributed_pytorch_tpu.parallel.mesh import mesh_for
    from distributed_pytorch_tpu.train.state import (build_model,
                                                     init_train_state,
                                                     make_optimizer,
                                                     state_shardings)
    from distributed_pytorch_tpu.train.step import make_train_step

    from benchmark.lib import harness
    t = res["traffic"]
    model_cfg = LLMConfig(**res["config"]["llm_config"])
    train_cfg = TrainConfig(**t["train_config"])
    chips = res["cell"]["chips"]
    mesh = mesh_for(train_cfg.parallelism, devices=topo.devices[:chips])
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model = build_model(model_cfg, train_cfg)
    tx = make_optimizer(train_cfg)

    def init_fn(r):
        return init_train_state(r, model, model_cfg, tx,
                                batch_size=train_cfg.batch_size)

    shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    sharding = state_shardings(shapes, train_cfg.parallelism, mesh)
    state = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, sharding)
    b_glob = train_cfg.batch_size * sizes["data"]
    accum = train_cfg.total_batch_size // (b_glob * model_cfg.block_size)
    bspec = shd.batch_pspec(train_cfg.parallelism, mesh, leading_accum=True)
    batch = jax.ShapeDtypeStruct((accum, b_glob, model_cfg.block_size),
                                 jnp.int32,
                                 sharding=NamedSharding(mesh, bspec))
    step = make_train_step(model, tx, model_cfg, train_cfg, mesh, sharding)
    t0 = time.perf_counter()
    compiled = step.lower(state, batch, batch).compile()
    _report(f"{res['cell']['name']} train.step on mesh {sizes}", compiled,
            time.perf_counter() - t0)


def check_serve(res, topo) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from distributed_pytorch_tpu.config import LLMConfig
    from distributed_pytorch_tpu.engine import decode as dec
    from distributed_pytorch_tpu.models.gpt import LLM, init_paged_cache

    from benchmark.lib import harness
    t = res["traffic"]
    e = t["engine"]
    cfg = LLMConfig(**res["config"]["llm_config"])
    model = LLM(cfg, compute_dtype=jnp.dtype(t["compute_dtype"]),
                attn_impl=t["attn_impl"])
    chip = SingleDeviceSharding(topo.devices[0])
    sds = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,  # noqa: E731
                                         sharding=chip)
    key = jax.random.PRNGKey(0)
    variables = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda k: model.init({"params": k, "dropout": k},
                             jnp.zeros((1, 8), jnp.int32)), key))
    n_slots, bs = e["n_slots"], e["block_size"]
    max_blocks = e["max_len"] // bs
    n_blocks = n_slots * max_blocks + 1
    n_blocks += (-n_blocks) % 8
    width = max_blocks + e["prefill_chunk"] // bs
    caches = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: init_paged_cache(cfg, n_blocks, bs,
                                 dtype=model.compute_dtype)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,  # noqa: E731
                                              sharding=chip)
    tok, pos = i32(n_slots), i32(n_slots)
    live = jax.ShapeDtypeStruct((n_slots,), jnp.bool_, sharding=chip)
    bt = i32(n_slots, width)
    rng = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=chip)
    sample = lambda logits, r: jnp.argmax(logits, axis=-1).astype(jnp.int32)  # noqa: E731
    scalar = i32()
    print(f"engine arguments: weights "
          f"{sum(v.size * v.dtype.itemsize for v in jax.tree_util.tree_leaves(variables)) / 2**30:.2f}"
          f" GiB, cache {n_blocks} blocks "
          f"{sum(v.size * v.dtype.itemsize for v in jax.tree_util.tree_leaves(caches)) / 2**30:.2f}"
          f" GiB", flush=True)
    step = jax.jit(dec.make_step_fn(model, sample), donate_argnums=(1,))
    t0 = time.perf_counter()
    compiled = step.lower(variables, caches, tok, pos, live, bt, rng, scalar,
                          None).compile()
    _report(f"{res['cell']['name']} engine.step", compiled,
            time.perf_counter() - t0)
    fused = jax.jit(dec.make_fused_step_fn(model, sample, n_slots, width),
                    donate_argnums=(1,))
    t0 = time.perf_counter()
    compiled = fused.lower(
        variables, caches, tok, pos, live, bt, rng, scalar, None,
        i32(1, e["prefill_chunk"]), scalar, scalar, i32(1),
        jax.ShapeDtypeStruct((), jnp.bool_, sharding=chip)).compile()
    _report(f"{res['cell']['name']} engine.fused_step", compiled,
            time.perf_counter() - t0)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--set", action="append", default=[], metavar="K=V",
                    help="override one train_config / engine entry of the "
                         "mix (JSON value), to try a fallback size")
    args = ap.parse_args()
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies
    from benchmark.lib import harness
    res = harness.resolve_cell(harness.load_benchmark(), args.workload)
    import json
    for kv in args.set:
        k, v = kv.split("=", 1)
        group = "train_config" if "train_config" in res["traffic"] \
            else "engine"
        res["traffic"][group][k] = json.loads(v)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    kind = res["traffic"]["kind"]
    if kind == "train":
        check_train(res, topo)
    elif kind.startswith("serve"):
        check_serve(res, topo)
    else:
        print(f"no compile check for runner kind {kind!r}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
