"""One collection rule for `benchmark/tests`, in a file of its own because
the files there may not be edited by the PR that needed it (33).

`tests/test_lib.py::test_flops_agree_with_the_program` runs over EVERY
configuration of BENCHMARK.json and holds `lib/flops.py` to the program's
`train/metrics.py`. Both model dense GPT-2 blocks only; `flops.py` says so
("a configuration with experts or latent attention brings its own functions
in a file of its own"). A patterned configuration (`llm_config` with a
`layer_pattern`) brings `lib/flops_hybrid.py`, which
`tests/test_hybrid.py::test_flops_count_the_tree` holds to the program's
parameter tree. Its case of the dense test is skipped, aloud; a `benchmark`
PR makes the parametrisation say the same (PERF.md section 7)."""

import pytest


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.originalname != "test_flops_agree_with_the_program":
            continue
        cfg = getattr(item, "callspec", None)
        cfg = cfg.params.get("config") if cfg else None
        if cfg and cfg.get("llm_config", {}).get("layer_pattern"):
            item.add_marker(pytest.mark.skip(
                reason="a patterned configuration: lib/flops.py models "
                       "dense blocks only; lib/flops_hybrid.py is held to "
                       "the program in test_hybrid.py"))
