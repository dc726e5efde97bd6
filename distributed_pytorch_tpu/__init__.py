"""distributed_pytorch_tpu — a TPU-native distributed LLM training framework.

A from-scratch JAX/XLA re-design of the capability surface of
Vineet314/Distributed-Pytorch (mounted read-only at /root/reference): a
nanoGPT-style LLM library (GQA/MQA/MHA and DeepSeek-V2 MLA attention,
RoPE/learned/sinusoidal positions, dense MLP and DeepSeekMoE feed-forward,
KV-cached generation) plus a single pjit-based trainer whose parallelism
strategies (the reference's single-GPU / DDP / ZeRO-1 / ZeRO-2 / FSDP entry
points, and beyond: TP / EP / sequence parallel) are *named sharding recipes*
— PartitionSpec tables over a `jax.sharding.Mesh` — rather than separate
trainers.

Design stance (see SURVEY.md §7): the reference's four trainers are ~85%
copy-paste and differ only in how tensors are sharded, which under GSPMD is
configuration, not code. Hence: ONE model library (`models/`), ONE trainer
(`train/`), ONE data pipeline (`data/`), and a recipe table (`parallel/`).
"""

__version__ = "0.1.0"

from distributed_pytorch_tpu.config import LLMConfig, TrainConfig  # noqa: F401
