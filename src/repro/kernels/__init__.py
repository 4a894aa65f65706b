"""Pallas TPU kernels for the perf-critical compute hot-spots, each with a
pure-jnp oracle in ref.py and a model-layout wrapper in ops.py:

* flash_attention — GQA/causal/sliding-window online-softmax attention
  (prefill/train hot-spot of the dense/moe/vlm/hybrid archs).
* ssd_scan — Mamba2 SSD chunk scan with VMEM-carried state (ssm/hybrid).
* adel_agg / adel_agg_q8 — the paper's layer-wise masked aggregation
  (server hot loop), over float and over int8 wire payloads.
* embed_grad — the token-embedding lookup's backward, a sorted segment sum
  into the f32 table (every transformer's local training on TPU); its
  oracle is ``jax.ops.segment_sum``, the CPU path.

All are checked against their oracles in interpret mode on CPU. The two
aggregation kernels and embed_grad are also compiled for a described TPU
v5e by tests/test_tpu_compile.py; chip_smoke.py runs the aggregation
kernels on the chip inside the temporal round step, and every round step
on a TPU runs embed_grad. flash_attention and ssd_scan are on no model
path and have only been run in interpret mode.
"""
from repro.kernels.adel_agg import adel_agg
from repro.kernels.embed_grad import embed_grad
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan

__all__ = ["adel_agg", "embed_grad", "flash_attention", "ssd_scan"]
