"""Model zoo covering the BASELINE workload configs (BASELINE.md):
1. LeNet (MNIST, static graph)      -> lenet.py
2. ResNet-50 (dygraph paddle.nn)    -> resnet.py
3/4. BERT/ERNIE transformer (static, SPMD-ready with TP rules) -> bert.py
5. Wide&Deep CTR (sparse embeddings) -> wide_deep.py
Plus a GPT-style causal-decoder LM (tied embeddings, pre-LN, causal flash
attention, TP rules) -> gpt.py, and SE-ResNeXt 50/101/152 (the reference's
canonical dist-test model, grouped convs + squeeze-excitation)
-> se_resnext.py.

Eight sparse causal LMs, each one expert-parallel rank's share of a published
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
layer sparse, a shared expert beside the routed ones), laguna.py (a query
head COUNT that differs with the layer's kind, 64 in the sliding-window
layers and 48 in the full ones on 8 KV heads, so groups of 8 and of 6 meet
in one step; rotary positions on the FIRST half of a head in the full
layers, `rotary_embedding`'s `rotary_start`, with yarn's table over that
half, all of a head in the sliding ones; the element-wise output gate; a
dense layer before sigmoid-routed experts of the narrowest width, 32 held a
rank, scaled by 2.5 beside a shared one).
What they share is written
once in causal_lm.py (the leaves, the expert layer around `routed_moe`,
attention on grouped KV heads, the layer loop, the loss); a model file holds
its configuration, the mixers of its own and which layer gets what.
"""
from . import (lenet, resnet, bert, wide_deep, gpt, se_resnext, causal_lm,
               deepseek_v3, mellum, nemotron_h, ling, keye, lfm2, solar,
               laguna)
