"""qwen1.5-4b [dense] — QKV bias [hf:Qwen/Qwen1.5-4B].

``PUBLISHED`` is the model as its ``config.json`` gives it. ``CONFIG`` —
what the registry serves under this name — is one TPU v5e chip's share of
a deployment of it, cut only in depth and in the vocabulary rows held:

* Deployment: the 40 layers run as 10 pipeline stages of 4 layers, one
  stage per group of chips, and the embedding and the LM head are split 8
  ways over the vocabulary. This chip holds one stage (4 layers) and one
  eighth of the vocabulary. The model is dense, so one layer is a whole
  period of the layer pattern.
* Every width is as published: d_model 2560, 20 query and 20 KV heads of
  128, SwiGLU d_ff 6912, QKV bias, rope_theta 5e6, RMSNorm eps 1e-6.
* A sliced vocabulary is a smaller vocabulary: ``vocab`` holds
  151936 / 8 = 18992 rows (``pad_vocab`` pads the tables to 19456), the
  logits and the loss are over the slice, and the synthetic traffic draws
  its ids from the first ``min(vocab, 2048)`` of them.

``ArchConfig.reduced()`` of this config is the CPU test toy (every width
cut); ``repro.launch.train.run_training(reduced=False)`` runs this share.
"""
import dataclasses

from .base import ArchConfig

PUBLISHED = ArchConfig(
    name="qwen1.5-4b", family="dense",
    L=40, d_model=2560, n_heads=20, n_kv=20, d_head=128,
    d_ff=6912, vocab=151936, qkv_bias=True,
    rope_mode="full", rope_theta=5_000_000.0, norm_eps=1e-6,
    source="hf:Qwen/Qwen1.5-4B",
)

CONFIG = dataclasses.replace(PUBLISHED, L=4, vocab=18_992)

# key -> (published, held on this chip)
REDUCED = {"L": (40, 4), "vocab": (151_936, 18_992)}

# sizes the published config does not fix, set here
ASSUMED = {
    "param_dtype": "float32 master weights (bfloat16 compute, "
                   "ArchConfig.dtype)",
    "tie_embeddings": False,
}

DEPLOYMENT = ("40 layers as 10 pipeline stages of 4 layers; embedding and "
              "LM head split 8 ways over the vocabulary; this chip holds "
              "one stage and one vocabulary slice")
