"""CLI entry point: `python -m distributed_pytorch_tpu --flags...`

Replaces all five reference trainer invocations (single-gpu/train.py,
torchrun'd multi-gpu/ddp/train.py, and the three kaggle scripts): the
parallelism strategy is `--parallelism {single,dp,zero1,zero2,fsdp,tp,
fsdp_tp,ep,sp,pp}` (axis sizes compose, e.g. --parallelism fsdp
--ep_size 2) instead of a choice of script, and there is no torchrun —
on a TPU pod every host runs this same command (see scripts/train.sh).
Flag surface mirrors the reference's ~33 argparse flags
(single-gpu/train.py:136-181), including --total_batch_size_str "2**14".

Ladder extras: `--preset gpt2_350m|gpt2_774m|gpt2_1p5b` (config.PRESETS)
seeds the model defaults with a BASELINE.json ladder rung — explicit
flags still override — and `--dryrun` prints the static HBM plan
(micro-batch, remat policy, est. peak HBM, grad-accum; train/memplan.py)
and exits without compiling anything.
"""

from distributed_pytorch_tpu.config import (PRESETS, build_parser,
                                            configs_from_args, knobs_table)


def parse_train_argv(argv):
    """(model_cfg, train_cfg) from a train command line, with the same
    preset re-parse `main` applies — the AOT pre-warm path
    (parallel/aot_store.py) resolves the exact configs a supervised
    worker would train under from its stored argv."""
    args = build_parser().parse_args(argv)
    model_defaults = None
    if args.preset:
        # re-parse against the preset's defaults so explicit flags win
        model_defaults = PRESETS[args.preset]()
        args = build_parser(model_defaults=model_defaults).parse_args(argv)
    return configs_from_args(args, model_defaults=model_defaults)


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    if args.knobs:
        # the registry is declared entirely in config.py — no jax import,
        # so this works anywhere the package installs
        print(knobs_table())
        return
    model_cfg, train_cfg = parse_train_argv(argv)

    if train_cfg.platform != "auto":
        # pin the backend BEFORE any jax device op (= JAX_PLATFORMS)
        import jax
        jax.config.update("jax_platforms", train_cfg.platform)

    if args.dryrun:
        from distributed_pytorch_tpu.parallel import shardcheck
        from distributed_pytorch_tpu.train.memplan import plan_memory
        plan = plan_memory(model_cfg, train_cfg,
                           preset_name=args.preset or "custom")
        print(plan.summary())
        # the same device-free spec validation the CI static-analysis
        # gate runs: a recipe/mesh mistake surfaces here, not on silicon
        report = shardcheck.check_train_config(
            model_cfg, train_cfg, preset=args.preset or "custom")
        print(shardcheck.format_report(report))
        return

    from distributed_pytorch_tpu.config import enable_compile_cache
    enable_compile_cache()
    from distributed_pytorch_tpu.train.loop import train
    train(model_cfg, train_cfg)


if __name__ == "__main__":
    main()
