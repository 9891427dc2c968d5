"""Plain float32 reference of a dense pre-norm decoder served over a
tensor-parallel mesh (``bench/models/dense_decoder_tp.py``).

It is the dense decoder's reference (``bench/reference/dense_decoder.py``),
unchanged: the same equations, one layer at a time in float32 at
``Precision.HIGHEST``.  Here it reads the weights where the adapter placed
them, sharded over the mesh, and XLA's partitioner splits its matmuls
over the same devices; a partitioned sum adds the same products in
another order, which float32 at ``HIGHEST`` does not tell apart from the
serving path's bf16 error.  It imports jax and numpy only, nothing of the
program.

For DeepSeek LLM (``deepseek-ai/deepseek-llm-67b-base``, a LLaMA
architecture) these are the published equations: RMSNorm with a learned
scale (eps 1e-6) before attention, before the FFN and before the head;
rotary embeddings in rotate-half form with theta 10000 on every query and
key head; causal softmax attention scaled by 1/sqrt(128), 64 query heads
over 8 KV heads (query head ``h`` reads KV head ``h // 8``); a SiLU-gated
FFN; an untied head; no biases anywhere.  No departure.
"""
from bench.reference.dense_decoder import logits  # noqa: F401
