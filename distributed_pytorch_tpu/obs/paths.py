"""Which code path a compiled program took — said once, where it is read.

The dispatchers in ops/ choose at TRACE time between a Pallas kernel and
an XLA/einsum path. A choice nobody sees lets a measurement time the
fallback under the kernel's name, so:

* `note(kind, path, why)` records a choice (a trace-time side effect,
  like `TraceGuard.mark`); `choices()` is what the train loop and the
  serve CLI print beside each program they compile (they `reset()` right
  before lowering it).
* `declined(what, gate, why)` is the error for a path that was asked for
  BY NAME (`attn_impl='pallas'`, `FLASH_DECODE=on`)
  and cannot run: it names the gate that declined.
* `compile_and_describe(jitted, *args)` is what the train loop and the
  engine call on each program they serve with; its record is what
  `stats.json:programs`, `spinup.jsonl` and chip_smoke.py read.
* `kernel_census(hlo_text)` reads the proof out of a compiled program:
  every Pallas kernel is a `tpu_custom_call` whose op_name ends in
  `<kernel name>/pallas_call` (each `pl.pallas_call` in ops/ carries a
  stable `name=`; autodiff wraps it as `transpose(jvp(<name>))`). A
  program compiled for the CPU holds none.
"""

from __future__ import annotations

import logging
import re

log = logging.getLogger("paths")

_choices: dict[str, dict[str, str]] = {}


def note(kind: str, path: str, why: str = "", *,
         replaces: tuple[str, ...] = ()) -> None:
    """Record that `kind` (attention | decode_attention | loss | moe)
    went down `path`; logged the first time each (kind, path) is seen.
    `replaces` names earlier, coarser notes of the same call that this one
    says more exactly (the loss: which rule the fused scan ran)."""
    seen = _choices.setdefault(kind, {})
    for coarser in replaces:
        seen.pop(coarser, None)
    if path not in seen:
        seen[path] = why
        log.info("[paths] %s -> %s%s", kind, path,
                 f" ({why})" if why else "")


def reset() -> None:
    """Forget what was traced so far — called right before a program is
    lowered, so `choices()` afterwards is THAT program's (shape probes such
    as memplan's eval_shape of a default model trace too)."""
    _choices.clear()


def choices() -> dict[str, str]:
    """{kind: "path (why) | path (why)"} for everything traced so far."""
    return {kind: " | ".join(f"{p} ({w})" if w else p
                             for p, w in seen.items())
            for kind, seen in _choices.items()}


class PathDeclined(ValueError):
    """A kernel requested by name was refused by its usable-gate."""


def declined(what: str, gate: str, why: str) -> PathDeclined:
    return PathDeclined(
        f"{what} was requested but {gate} declined: {why}. Ask for 'auto' "
        "to let the dispatcher choose (its choice is logged), or fix the "
        "shape/mesh the gate names.")


# the kernel's name is the last scope before /pallas_call; under autodiff
# jax wraps it: ".../transpose(jvp(flash_bwd_dq))/pallas_call"
_KERNEL_RE = re.compile(r'op_name="[^"]*?([A-Za-z0-9_]+)\)*/pallas_call')


def kernel_census(hlo_text: str) -> dict[str, int]:
    """{kernel name: count} over the `tpu_custom_call`s of a compiled
    program's text (`compiled.as_text()`)."""
    census: dict[str, int] = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _KERNEL_RE.search(line)
        name = m.group(1) if m else "unnamed"
        census[name] = census.get(name, 0) + 1
    return census


_COLLECTIVE_RE = re.compile(
    r"\s(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_FUSED_RS_RE = re.compile(r"^%?all-reduce-scatter[.\w]* \(", re.M)


def collective_census(hlo_text: str) -> dict[str, int]:
    """{collective: count} over a compiled program's text; an async pair
    counts once (its -start). The TPU compiler spells a reduce-scatter as
    a fused computation named `all-reduce-scatter`, counted here under
    'reduce-scatter' beside any plain ones."""
    census: dict[str, int] = {}
    for op in _COLLECTIVE_RE.findall(hlo_text):
        census[op] = census.get(op, 0) + 1
    fused = len(_FUSED_RS_RE.findall(hlo_text))
    if fused:
        census["reduce-scatter"] = census.get("reduce-scatter", 0) + fused
    return census


def device_record() -> dict:
    """The device a process ran on, as jax reports it — the three keys
    every result line carries."""
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def compile_and_describe(jitted, *args) -> dict:
    """Lower + compile `jitted` for `args` (arrays or ShapeDtypeStructs)
    NOW and say what is in the program before it runs: compile seconds,
    `describe_compiled`'s facts, and the paths the dispatchers chose while
    THIS program traced. A later `jitted(*args)` call reuses the trace
    (and, with matching argument placement, the executable), so this moves
    the first call's compile here rather than adding one."""
    import time
    reset()
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    return {"compile_s": round(time.perf_counter() - t0, 3),
            **describe_compiled(compiled), "paths": choices()}


def describe_compiled(compiled) -> dict:
    """The facts a bring-up reads off one compiled program: its Pallas
    kernels, its collectives and the compiler's own memory accounting
    (bytes)."""
    hlo = compiled.as_text()
    out = {"kernels": kernel_census(hlo),
           "collectives": collective_census(hlo)}
    mem = compiled.memory_analysis()
    if mem is not None:
        out["temp_bytes"] = int(mem.temp_size_in_bytes)
        out["argument_bytes"] = int(mem.argument_size_in_bytes)
        out["output_bytes"] = int(mem.output_size_in_bytes)
        out["alias_bytes"] = int(mem.alias_size_in_bytes)
    return out
