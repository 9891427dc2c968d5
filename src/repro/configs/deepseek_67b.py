"""deepseek-67b — dense llama-arch GQA.

Follows DeepSeek LLM 67B Base's config.json
(https://huggingface.co/deepseek-ai/deepseek-llm-67b-base; arXiv:2401.02954):
95L d_model=8192 64H (GQA kv=8) d_ff=22016 vocab=102400, RMSNorm eps 1e-6,
RoPE theta 10000, 4096 positions, untied head.  The benchmarked cut (16
layers as one pipeline stage, 4-way tensor parallel on one four-chip host)
is ``bench/configs/deepseek-67b-tp4.json``.
"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="deepseek-67b",
    family="dense",
    num_layers=95,
    d_model=8192,
    num_heads=64,
    num_kv_heads=8,
    head_dim=128,
    d_ff=22016,
    vocab_size=102_400,
)
