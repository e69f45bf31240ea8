"""Plain reference of `nemotron3_super_120b_a12b_ep64_tp8`: one chip's share
of the decoder that nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16's
`config.json` describes (`model_type` nemotron_h), in straightforward
float32 `jax.numpy`: forward, next-token loss, gradients and Adam.

No kernels, no AMP, no chunks, no sort and no grouped matmul. Matmuls run at
precision `highest`. Nothing is imported from the program. The state-space
mixer, attention, the norms, the router and the Adam step are
`reference/nemotron_h.py`'s functions, called as they are: each takes its
share as arguments (the configuration's top-level keys say what is HELD:
`mamba_num_heads` 16 in `n_groups` 1, `num_attention_heads` 4 on
`num_key_value_heads` 1). The expert layer, the leaves and their seeded
draws are this file's. The layer equations (x `[S, d]`, d = `hidden_size`
4096; no dropout, untied head):

Layer n of 88: x <- x + Mixer_n(RMSNorm(x)), eps `layer_norm_epsilon`
1e-5, the kind of Mixer_n the n-th letter of `hybrid_override_pattern`;
after the last layer a final RMSNorm, then the head over the `vocab` rows
held.

* `M`, Mamba-2. Published H = 128 heads of P = 64 in G = 8 groups, state
  N = 128, conv K = 4, d_in = H P = 8192. [z | xBC | dt] = u W_in, published
  columns [z 8192 | x 8192 | B 8 x 128 | C 8 x 128 | dt 128] = 18,560, no
  bias. xBC <- silu(conv(xBC) + b), depthwise and causal. Head h reads the
  B, C of group h // 16. dt <- softplus(dt + dt_bias), A = -exp(A_log).
  Per head, from a zero state [P, N]:
      h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t
      y_t = h_t C_t + D x_t
  y <- GroupRMSNorm(y * silu(z)) over each group's 1,024 channels, times a
  weight, eps 1e-5. out = y W_out. HELD: heads 0..15 = group 0: columns
  [z 1024 | x 1024 | B 128 | C 128 | dt 16] = 2,320, W_out's rows 0..1,023:
  13,708,592 parameters a layer with its norm.
* `*`. 32 query heads on 2 KV heads of 128, query head h on KV head
  h // 16, causal, scale 128^-0.5, no bias, NO rotary positions. HELD: query
  heads 0..3 on KV head 0 (all four read it): 5,246,976 parameters.
* `E`, experts in a latent. s = sigmoid(u W_r) over ALL 512 experts, in
  float32; the 22 largest of s + b (b a selection bias no gradient reaches;
  `n_group` = `topk_group` = 1: no grouping); their weights s there divided
  by their sum (`norm_topk_prob`) and times `routed_scaling_factor` 5.
  z = u W_a, W_a [4096, 1024] (`moe_latent_size`). Expert e:
  E_e(z) = W_down,e relu(W_up,e z)^2, W_up,e [1024, 2688], W_down,e
  [2688, 1024]. y = (sum_k w_k E_{i_k}(z)) W_b + Shared(u), W_b
  [1024, 4096], Shared(u) = W_down relu(W_up u)^2 at width 5376 on the full
  4096. Nothing (no norm, bias or activation) stands between the two
  projections and the experts; the router and the shared expert read u and
  not z. HELD: experts `expert_offset` .. + `n_routed_experts` (0..7), whose
  terms alone are summed before W_b; with `n_routed_experts` =
  `experts_total` the same code is the uncut layer. 98,570,752 parameters a
  layer with its norm.

Departures from the published model, each also in the configuration file's
`assumed`:

* the multi-token head (`num_nextn_predict_layers` 1,
  `mtp_hybrid_override_pattern` `*E`) is NOT built: its block does not fit
  beside this share and lies on the deployment's last pipeline stage.
* d_in = `mamba_num_heads` x `mamba_head_dim`, as the family's code reads
  it; `expand` is not read. No rotary positions: `rope_theta` and
  `partial_rotary_factor` are kept as published and not read.
* where the latent's two projections sit, and that the router and the
  shared expert read the full width: `config.json` names only the latent's
  width; this reading makes the catalog's two parameter counts come out.
* the selection bias is held fixed (its update rule is not in
  `config.json`) and seeded as a spread of width `select_bias_std`.
* the loss is the mean over the labelled positions' cross entropy, every
  position but a row's last carrying the next token (label -100 = none); no
  auxiliary balance loss.

`quant` names the control: "fp8" rounds both operands of every product the
configuration runs in bf16 to float8_e4m3, the step below; what the
configuration states in float32 stays as it is. Faults are switched from the
configuration (absent in its file): `num_experts_per_tok` 6 or
`routed_scaling_factor` 1 in place of the published numbers;
`assumed.routed_left_out` "l3_" leaves the routed part of that expert layer
out; `assumed.scan_state_dtype` rounds the recurrent state after every
token to that type.

The model around the expert layer (`layer`, `forward`, `loss_sum`, the
gradient in blocks of rows, `follow`) and the rules of the seeded draws
(`init_leaf`) are `reference/nemotron_h.py`'s code run over THIS module's
names (`_over_this_module`, each with the names it replaces): the same text,
this file's `expert_layer`, `param_shapes` and `_OUT_PROJECTIONS`.
"""
from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp

from . import nemotron_h as base
from .nemotron_h import (ADAM, EXPERTS, IGNORE, _adam, _cfg_key,  # noqa: F401
                         _mm, buffer_shapes, layer_kinds, relu2_ffn, route)


def param_shapes(cfg: dict) -> dict:
    """Every leaf Adam trains: `reference/nemotron_h.py`'s leaves at the
    held heads and groups, the experts at the latent's width, and the two
    projections into and out of it."""
    d, lat = cfg["hidden_size"], cfg["moe_latent_size"]
    held, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    s = base.param_shapes(cfg)
    for n, kind in enumerate(layer_kinds(cfg)):
        if kind == EXPERTS:
            p = f"l{n}_"
            s.update({p + "latent_down_w": (d, lat),
                      p + "latent_up_w": (lat, d),
                      p + "experts_up_w": (held, lat, f),
                      p + "experts_down_w": (held, f, lat)})
    return s


# divided by sqrt(`num_hidden_layers`) (`rescale_prenorm_residual`): every
# projection back into the residual stream, W_b among them
_OUT_PROJECTIONS = base._OUT_PROJECTIONS + ("latent_up_w",)


def held_experts(z, idx, w, p, pre, cfg, quant=None):
    """sum over the held experts e of w_e(t) E_e(z_t), in the latent: a
    loop (`lax.scan`) over the held experts, each on every token, weighted
    by a mask. idx, w [T, k]: the choice over ALL the experts."""
    held = cfg.get("expert_offset", 0) + jnp.arange(cfg["n_routed_experts"])
    # w_e[e, t]: the weight token t gives held expert e, 0 if not chosen
    w_e = jnp.sum(jnp.where(idx[None] == held[:, None, None], w[None], 0.0),
                  axis=2)

    def one_expert(out, e):
        up, down, weight = e
        return out + weight[:, None] * relu2_ffn(z, up, down, quant), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(z),
        (p[pre + "experts_up_w"], p[pre + "experts_down_w"], w_e))
    return out


def expert_layer(u, p, pre, cfg, quant=None):
    """(routed W_b + Shared(u), idx): three plain products around the held
    experts' sum, the router and the shared expert on the full width."""
    ut = u.reshape(-1, u.shape[-1])
    idx, w = route(ut, p[pre + "router_w"], p[pre + "router_bias"], cfg)
    z = _mm(ut, p[pre + "latent_down_w"], quant)
    summed = held_experts(z, idx, w, p, pre, cfg, quant)
    if cfg["assumed"].get("routed_left_out") == pre:    # a fault
        summed = jnp.zeros_like(summed)
    y = _mm(summed, p[pre + "latent_up_w"], quant) + relu2_ffn(
        ut, p[pre + "shared_up_w"], p[pre + "shared_down_w"], quant)
    return y.reshape(u.shape), idx


def _over_this_module(fn, **own):
    """`fn` of `reference/nemotron_h.py`: its code over that module's names
    with `own` in their place."""
    return types.FunctionType(fn.__code__, {**vars(base), **own},
                              fn.__name__, fn.__defaults__)


init_leaf = _over_this_module(base.init_leaf, param_shapes=param_shapes,
                              _OUT_PROJECTIONS=_OUT_PROJECTIONS)
init_params = _over_this_module(base.init_params, init_leaf=init_leaf,
                                param_shapes=param_shapes)
split_state = base.split_state
layer = _over_this_module(base.layer, expert_layer=expert_layer)
forward = _over_this_module(base.forward, layer=layer)
loss_sum = _over_this_module(base.loss_sum, forward=forward)
_block_grad = functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))(
    _over_this_module(base._block_grad.__wrapped__, loss_sum=loss_sum))
follow = _over_this_module(base.follow, _block_grad=_block_grad)
