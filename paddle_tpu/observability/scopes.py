"""Device-side scopes: the catalogue of every name the package puts into an
instruction's `op_name`, and the classification of an `op_name` into
(phase, layer scope).

Two kinds of name stand in the `op_name` metadata of a compiled step
(docs/observability.md, "Device-side scopes"):

* the PHASE, opened by the executor outside every op's lowering from the
  op's `op_role` (`framework/executor.py` `op_scopes`): `phase.fwd`,
  `phase.bwd`, `phase.opt`. The forward run again under recomputation is
  not named by the program: JAX marks it `rematted_computation` inside
  `jax.checkpoint`'s backward.
* the LAYER scope, `program.name_scope` of the builders and the
  `jax.named_scope`s of the ops' own lowerings (`CATALOGUE`).

A forward scope's name also stands INSIDE a backward instruction's
`op_name` (`phase.bwd/transpose(jvp(phase.fwd))/...`), so no substring
decides the phase: `classify` reads the path.
"""
from __future__ import annotations

import re
from typing import NamedTuple

PHASE_FWD, PHASE_BWD, PHASE_OPT = "phase.fwd", "phase.bwd", "phase.opt"
PHASES = {PHASE_FWD: "fwd", PHASE_BWD: "bwd", PHASE_OPT: "opt"}
# what `jax.checkpoint` names the forward it runs again in the backward
REMAT_MARK = "rematted_computation"
NONE = "none"


class Scope(NamedTuple):
    layer: str      # the `layer` of BENCHMARK.json's per_layer it belongs to
    set_by: str     # where the name is set


def _scopes(layer: str, set_by: str, *names: str) -> dict:
    return {name: Scope(layer, set_by) for name in names}


CATALOGUE = {
    **_scopes("Embedding and head", "models/causal_lm.py, models/bert.py",
              "embed.tokens"),
    **_scopes("Embedding and head", "models/causal_lm.py",
              "head.norm", "head.untied", "head.tied", "head.loss"),
    **_scopes("Embedding and head", "models/bert.py", "head.mlm"),
    **_scopes("Residual stream", "models/causal_lm.py, models/bert.py",
              "layer.residual"),
    **_scopes("Dense feed-forward", "models/causal_lm.py, models/bert.py",
              "ffn.dense"),
    **_scopes("Dense feed-forward", "models/causal_lm.py", "moe.shared"),
    **_scopes("Attention projections", "models/causal_lm.py, models/bert.py",
              "attn.proj"),
    **_scopes("Attention projections", "models/causal_lm.py", "attn.qk_norm"),
    **_scopes("Attention projections", "models/bert.py", "attn.mask"),
    **_scopes("Attention projections", "models/deepseek_v3.py, models/ling.py",
              "mla.proj"),
    **_scopes("Kernels", "models/deepseek_v3.py, models/ling.py",
              "mla.attend"),
    **_scopes("Kernels", "models/causal_lm.py, models/bert.py",
              "attn.attend.full"),
    **_scopes("Kernels", "models/causal_lm.py",
              "attn.attend.window", "attn.attend.sparse"),
    **_scopes("Sparse-attention indexer",
              "models/keye.py, ops/sparse_index.py", "attn.index.score"),
    **_scopes("Sparse-attention indexer", "ops/sparse_index.py",
              "attn.index.select", "attn.index.loss"),
    **_scopes("Sparse-attention indexer", "ops/attention.py",
              "attn.index.target"),
    **_scopes("Sparse-attention indexer", "models/keye.py", "dsa.io"),
    **_scopes("Expert layer", "ops/moe.py",
              "moe.route", "moe.dispatch", "moe.experts", "moe.combine"),
    **_scopes("Expert layer", "models/causal_lm.py", "moe.io"),
    **_scopes("Expert layer", "models/bert.py", "moe.switch"),
    **_scopes("Expert layer", "models/nemotron_h.py",
              "moe.latent_down", "moe.latent_up"),
    **_scopes("State-space layer", "models/nemotron_h.py",
              "ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate_norm",
              "ssm.out_proj"),
    **_scopes("State-space layer", "ops/ssm.py",
              "ssm.scan.states", "ssm.scan.carry", "ssm.scan.inter",
              "ssm.scan.intra"),
    **_scopes("Short-convolution layer", "models/lfm2.py",
              "conv.in_proj", "conv.mix", "conv.out_proj"),
    **_scopes("Linear-attention layer", "models/ling.py, models/solar.py",
              "kda.proj", "kda.gate", "kda.scan", "kda.out"),
    **_scopes("Linear-attention layer", "models/causal_lm.py", "kda.conv"),
    **_scopes("Linear-attention layer", "ops/kda.py",
              "kda.scan.intra", "kda.scan.solve", "kda.scan.carry",
              "kda.scan.inter"),
    # `optimizer.<type>` of every update op: any name under the prefix
    **_scopes("Optimizer", "optimizer.py", "optimizer.*"),
}
_OPTIMIZER = "optimizer."

# an op_name is a path of names; JAX wraps a name in `jvp(...)`,
# `transpose(...)` where a transform was applied to what lies under it
_NAMES = re.compile(r"[^/()]+")


def scope_of(name: str):
    """The catalogue's key for one name of an `op_name`, or None."""
    if name in CATALOGUE:
        return name
    if name.startswith(_OPTIMIZER):
        return _OPTIMIZER + "*"
    return None


def _top_level(op_name: str):
    """The names of the path that no transform wraps."""
    depth, start = 0, 0
    for i, c in enumerate(op_name):
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        elif c == "/" and depth == 0:
            yield op_name[start:i]
            start = i + 1
    yield op_name[start:]


def classify(op_name: str) -> tuple:
    """(phase, scope) of an instruction from its `op_name`.

    phase: `recompute` where JAX's mark stands (the forward run again in a
    checkpoint's backward); else the executor's: the OUTERMOST phase name
    that no transform wraps, as `fwd`, `bwd` or `opt` (a backward
    instruction of a segment reads `phase.bwd/transpose(jvp(phase.fwd))`:
    the wrapped name says what was differentiated, not what runs); else
    `none`. scope: the innermost catalogued name, wrapped or not (a
    differentiated forward reads `phase.fwd/jvp(moe.experts)/dot_general`);
    else `none`."""
    names = _NAMES.findall(op_name)
    scope = next((s for s in map(scope_of, reversed(names)) if s), NONE)
    if REMAT_MARK in names:
        return "recompute", scope
    phase = next((PHASES[n] for n in _top_level(op_name) if n in PHASES),
                 NONE)
    return phase, scope
