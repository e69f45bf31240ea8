"""Model zoo covering the BASELINE workload configs (BASELINE.md):
1. LeNet (MNIST, static graph)      -> lenet.py
2. ResNet-50 (dygraph paddle.nn)    -> resnet.py
3/4. BERT/ERNIE transformer (static, SPMD-ready with TP rules) -> bert.py
5. Wide&Deep CTR (sparse embeddings) -> wide_deep.py
Plus a GPT-style causal-decoder LM (tied embeddings, pre-LN, causal flash
attention, TP rules) -> gpt.py, and SE-ResNeXt 50/101/152 (the reference's
canonical dist-test model, grouped convs + squeeze-excitation)
-> se_resnext.py.

Seven sparse causal LMs, each one expert-parallel rank's share of a published
configuration, trained: deepseek_v3.py (latent attention, sigmoid-routed
experts without drops, shared experts), mellum.py (sliding-window and full
attention in a period, grouped KV heads, yarn, softmax-routed experts),
nemotron_h.py (a Mamba-2 mixer, ungated relu^2 experts with a shared one,
optionally in a latent, or attention without rotary positions a layer),
ling.py (Kimi-delta linear attention with latent attention closing every
group, head-wise gates, group-limited routing), keye.py (attention over a
learned SELECTION of keys: an indexer of a few small heads scores every
causal pair, each query attends its `topk` best-scored keys, all its heads
the same ones, and the indexer is trained towards the attention's own
probabilities; the selection is an int8 variable [B, S, S], one a row,
`layers.sparse_index`'s output and `layers.fused_attention`'s `select`
input; three-stream rotary positions; softmax-routed experts), lfm2.py
(gated short-convolution mixers, `C * conv(B * u)` between two projections
with no attention, 3 : 1 with attention on grouped KV heads under a
PER-HEAD NORM: q and k RMS-normed over each head's features, one learned
scale of `head_dim` shared by the heads, before the rotary turn; a TIED
HEAD: the logits are `norm(x) E^T` with E the token embedding itself, one
parameter read by a gather and by a matmul, its gradient the sum of both;
sigmoid-routed experts with a selection bias after leading dense layers),
solar.py (Kimi-delta linear attention in its ORIGINAL form, 3 : 1 with
softmax attention on grouped KV heads without rotary positions under an
element-wise output gate: the decay `-exp(A_log) softplus(.)` has no lower
bound, so `kda_scan` makes a chunk's decayed products level by level with no
factor above 1; `beta` in (0, 2); both gates' projections low-rank; every
layer sparse, a shared expert beside the routed ones).
What they share is written
once in causal_lm.py (the leaves, the expert layer around `routed_moe`,
attention on grouped KV heads, the layer loop, the loss); a model file holds
its configuration, the mixers of its own and which layer gets what.
"""
from . import (lenet, resnet, bert, wide_deep, gpt, se_resnext, causal_lm,
               deepseek_v3, mellum, nemotron_h, ling, keye, lfm2, solar)
