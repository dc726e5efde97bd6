"""Thin names over the installed JAX (0.9.0).

One installation is supported — jax/jaxlib 0.9.0, libtpu 0.0.34 — and
every function here is a direct call into it. The module remains only so
call sites (and the commscheck/shardcheck goldens traced through them)
keep their import names; ROADMAP D6 deletes it outright.

* `shard_map(f, mesh=..., in_specs=..., out_specs=...)` — `jax.shard_map`
  with replication checking (`check_vma`) defaulting OFF: the explicit
  out_specs already pin the output sharding, and the collective-matmul /
  ring-attention bodies compose custom_vjp with ppermute.
* `vma_of(x)` / `pcast_varying(x, vma)` — varying-manual-axes
  introspection and promotion for pallas calls inside shard_map.
* `tpu_compiler_params(**kw)` — `pltpu.CompilerParams`, with the scoped-
  VMEM limit every kernel's gate budgets against (`VMEM_LIMIT_BYTES`)
  handed to Mosaic.
* `request_cpu_devices(n)` — `jax_num_cpu_devices`, before any device op.
"""

from __future__ import annotations

from typing import Any

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def vma_of(x: Any):
    """The varying-manual-axes set of `x`'s type."""
    return jax.typeof(x).vma


def pcast_varying(x: Any, vma):
    """Promote `x` to vary over mesh axes `vma` (jax.lax.pcast); identity
    when vma is empty."""
    if not vma:
        return x
    return jax.lax.pcast(x, tuple(vma), to="varying")


def distributed_is_initialized() -> bool:
    """Touches no backend — safe before jax.distributed.initialize()."""
    return bool(jax.distributed.is_initialized())


# The scoped-VMEM limit every Pallas kernel in ops/ hands Mosaic AND the
# budget their `*_decline` gates check (ops/flash_attention.py and
# ops/flash_decode.py import this name): half of a v5e core's 128 MiB, the
# one chip this tree supports. One number, so "the gate says it fits" and
# "the compiler accepts it" stay one statement.
VMEM_LIMIT_BYTES = 64 * 2 ** 20


def tpu_compiler_params(**kwargs):
    """`pltpu.CompilerParams` for every Pallas kernel in ops/.

    Mosaic's default scoped-VMEM limit on v5e is 16 MiB, an eighth of the
    core's 128 MiB; the kernels' tile sizes and their gates were budgeted
    against VMEM_LIMIT_BYTES (64 MiB). Left at the default, flash
    backward, the CE backward, the int8 contiguous decode and the 512-row
    chunk prefill are all refused at flagship widths ("Scoped allocation
    ... exceeded scoped vmem limit", device-free v5e compile)."""
    from jax.experimental.pallas import tpu as pltpu
    kwargs.setdefault("vmem_limit_bytes", VMEM_LIMIT_BYTES)
    return pltpu.CompilerParams(**kwargs)


def request_cpu_devices(n: int) -> None:
    """Ask for `n` virtual CPU devices. Call BEFORE any jax device op."""
    jax.config.update("jax_num_cpu_devices", n)
