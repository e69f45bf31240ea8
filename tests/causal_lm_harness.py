"""What the tests of the sparse causal LMs share (`tests/test_deepseek_v3.py`,
`test_mellum.py`, `test_nemotron_h.py`, `test_nemotron3_super.py`,
`test_ling.py`, `test_causal_lm_steps.py`): the seeded batches, the program
trained from the reference's weights, the reference's own Adam steps, an op
run through its lowering, a share of `routed_moe` through a Program, the tiny
AMP step a census reads, and the digests that hold a step to the tree before.
Not collected: pytest takes `test_*.py`. Parametrised by the model module and
its reference; tolerances, fault tables and their reasons stay in the file
whose architecture they describe.
"""
import hashlib
import json
import os
import re
import sys

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.fluid as fluid  # noqa: E402
from paddle_tpu.distributed import fleet  # noqa: E402
from paddle_tpu.fluid import layers  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402
from paddle_tpu.ops import registry  # noqa: E402
from paddle_tpu.testing import reset_programs  # noqa: E402

S, B = 32, 4


# ---------------------------------------------------------------------------
# the program beside its reference
# ---------------------------------------------------------------------------

def batches(vocab, k, seed=0):
    """(ids, labels) [k, B, S]: seeded tokens, a row's last label ignored."""
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, vocab, (k, B, S)).astype(np.int64)
    labels = np.concatenate([ids[:, :, 1:], np.full((k, B, 1), -100)], 2)
    return ids, labels


def train_step(module, cfg, amp, recompute=False, lr=1e-3, built=None):
    """(executor, loss, routed) of `module`'s causal LM at `cfg`, its train
    step built through fleet (Adam; AMP if `amp`; a checkpoint at every
    layer boundary if `recompute`), the startup program run. `built(main
    program)` is called between the builder and the optimizer."""
    reset_programs(0)
    _, loss, routed = module.build_causal_lm_program(cfg)
    if built:
        built(fluid.default_main_program())
    fleet.init(is_collective=True)
    strategy = fleet.DistributedStrategy()
    strategy.amp = amp
    if recompute:
        strategy.recompute = True
        strategy.recompute_configs = {
            "checkpoints": list(loss._layer_checkpoints)}
    fleet.distributed_optimizer(paddle.optimizer.Adam(learning_rate=lr),
                                strategy).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    return exe, loss, routed


def amp_step(module, cfg, recompute=False):
    """(executor, loss, ids [2, 1, seq_len]) of the AMP train step a census
    traces (`train_step`), with seeded tokens of a vocabulary of 256."""
    exe, loss, _ = train_step(module, cfg, True, recompute)
    ids = np.random.RandomState(0).randint(
        0, 256, (2, 1, cfg.seq_len)).astype(np.int64)
    return exe, loss, ids


def trained_program(module, cfg, ref, params, amp, k, ids):
    """The program's losses, first routed choice and scope after `k` steps
    of `run_steps` from the reference's seeded weights `params`."""
    exe, loss, routed = train_step(module, cfg, amp, lr=ref.ADAM["lr"])
    scope = fluid.global_scope()
    for name, value in params.items():
        assert tuple(scope.find(name).shape) == tuple(value.shape), name
        scope.set(name, value)
    out = exe.run_steps(k, feed={"tokens": ids[:k]},
                        fetch_list=[loss, routed[0][0]])
    return np.asarray(out[0]).reshape(-1), np.asarray(out[1]), scope


def reference_states(ref, cfg, state, k, ids, labels):
    """[(loss, grads, params, m, v) after each of k reference steps] and the
    first step's routed choice. `state`: (params, *buffers) as the
    reference's `_block_grad` takes them (`ref.split_state`, or the
    parameters alone where it has no buffers)."""
    params, buffers = state[0], tuple(state[1:])
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    key = ref._cfg_key(cfg)
    states, first_idx = [], None
    for t in range(k):
        val, idx, grads = ref._block_grad(params, *buffers, ids[t],
                                          labels[t], key, None)
        n = float((labels[t] != -100).sum())
        grads = jax.tree.map(lambda g: g / n, grads)
        first_idx = idx if first_idx is None else first_idx
        copy = jax.tree.map(jnp.array, (params, m, v))
        params, m, v = ref._adam(*copy, grads, float(t + 1))
        states.append((float(val) / n, grads, params, m, v))
    return states, np.asarray(first_idx)


def rel_gap(got, want, *floors):
    """|got - want| over |want| (or the largest of `floors`, at least
    1e-20), in the 2-norm."""
    got, want = np.asarray(got, np.float32), np.asarray(want)
    return np.linalg.norm(got - want) / max(
        np.linalg.norm(want), 1e-20, *floors)


def first_step_gaps(scope, grads, ref):
    """{leaf: gap of the program's gradient to the reference's}: after ONE
    step Adam's first moment is (1 - beta1) x the gradient, leaf by leaf."""
    return {name: rel_gap(np.asarray(scope.find(name + "_moment1_0"),
                                     np.float32) / (1 - ref.ADAM["beta1"]),
                          want) for name, want in grads.items()}


def second_step_gaps(scope, states, p0, floor_by_first_step=False):
    """After two steps, for every leaf: (name, the largest gap of one of
    its elements, the norm of its gap, the norm of what the reference moved
    it from `p0`, {accumulator: `rel_gap` to the reference's}). With
    `floor_by_first_step` an accumulator's gap is taken against its size
    after either step."""
    (_, _, _, m1, v1), (_, _, params, m, v) = states
    for name in params:
        got = np.asarray(scope.find(name), np.float32)
        want = np.asarray(params[name])
        moments = {acc: rel_gap(
            scope.find(name + acc), after[name],
            *([np.linalg.norm(np.asarray(first[name]))]
              if floor_by_first_step else []))
            for acc, after, first in (("_moment1_0", m, m1),
                                      ("_moment2_0", v, v1))}
        yield (name, np.abs(got - want).max(), np.linalg.norm(got - want),
               np.linalg.norm(want - np.asarray(p0[name])), moments)


def route_mismatch(idx, ref_idx):
    """The share of (token, slot) choices, each token's sorted, that are
    not the reference's."""
    return (np.sort(idx.reshape(ref_idx.shape), 1)
            != np.sort(ref_idx, 1)).mean()


def worst_leaf_gap(ref, good_cfg, bad_cfg, state, ids, labels):
    """The largest gap of a leaf's gradient between the reference at
    `bad_cfg` and at `good_cfg`, on one batch from one `state`."""
    _, _, want = ref._block_grad(*state, ids, labels,
                                 ref._cfg_key(good_cfg), None)
    _, _, got = ref._block_grad(*state, ids, labels, ref._cfg_key(bad_cfg),
                                None)
    return max(float(jnp.linalg.norm(got[n] - want[n])
                     / jnp.linalg.norm(want[n])) for n in want)


# ---------------------------------------------------------------------------
# ops and parts of a layer through a Program
# ---------------------------------------------------------------------------

def run_op(op_type, inputs, outputs, attrs):
    """The named outputs of one op's lowering on arrays."""
    ctx = registry.LowerCtx(rng_key=jax.random.key(0))
    got = registry.get(op_type).lower(
        ctx, {k: [jnp.asarray(v)] for k, v in inputs.items()}, attrs)
    return [np.asarray(got[o][0]) for o in outputs]


def counter_rise(fn, names):
    """(fn(), the rise of each of the counters `names` while it ran)."""
    before = [metrics.get(n) for n in names]
    out = fn()
    return out, tuple(int(metrics.get(n) - b) for n, b in zip(names, before))


# what only `routed_moe`'s grad rule reads of its outputs
RULE_ONLY_OUTPUTS = ("H", "U", "SortedW", "Order", "Inv")


def withhold_residuals(program):
    """Take the outputs only the grad rule reads off every `routed_moe`: a
    program built before they existed, whose backward is the generic
    route's (the forward lowered again)."""
    for op in program.global_block().ops:
        if op.type == "routed_moe":
            for slot in RULE_ONLY_OUTPUTS:
                op.outputs.pop(slot, None)


def routed_share(x, arrays, top_k, total, offset, z=None, cot=None,
                 withhold=False, **options):
    """One share's `routed_moe` through a Program. `arrays`: the weights
    this share holds by short name, `gate_w` [d, total], `eu` / `ed` and,
    with a gate, `eg` [held, ...], and `bias` [total] where the selection
    has one; `z` [N, d_e] the experts' own input (None: x); `options` the
    layer's others (`scoring`, `routed_scaling`, `norm_topk`, `n_group`,
    `topk_group`). Returns [Out, TopIdx, ExpertLoad], or with `cot` the
    gradients of sum(Out * cot) with respect to (x, gate_w, [eg,] eu, ed
    [, z]), by the generic route if `withhold`."""
    reset_programs(0)
    xv = layers.data(name="x", shape=[x.shape[1]], dtype="float32")
    xv.stop_gradient = False
    feed, zv = {"x": x}, None
    if z is not None:
        zv = layers.data(name="z", shape=[z.shape[1]], dtype="float32")
        zv.stop_gradient = False
        feed["z"] = z
    weights = [k for k in ("gate_w", "eg", "eu", "ed") if k in arrays]
    var = {k: layers.create_parameter(list(arrays[k].shape), "float32",
                                      name=k) for k in weights}
    if "bias" in arrays:
        var["bias"] = layers.create_parameter([total], "float32",
                                              name="bias")
        var["bias"].stop_gradient = True
    out, idx, load = layers.routed_moe(
        xv, var["gate_w"], var.get("eg"), var["eu"], var["ed"], top_k=top_k,
        select_bias=var.get("bias"), experts_total=total,
        expert_offset=offset, expert_input=zv, **options)
    fetch = [out, idx, load]
    if cot is not None:
        cv = layers.data(name="cot", shape=[cot.shape[1]], dtype="float32")
        loss = layers.reduce_sum(layers.elementwise_mul(out, cv))
        if withhold:
            withhold_residuals(fluid.default_main_program())
        fetch = fluid.gradients(
            loss, [xv] + [var[k] for k in weights] + ([zv] if zv else []))
        feed["cot"] = cot
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    for k in var:
        fluid.global_scope().set(k, jnp.asarray(arrays[k]))
    return [np.asarray(g) for g in exe.run(feed=feed, fetch_list=fetch)]


def held_arrays(params, offset, held, bias=True):
    """`routed_share`'s `arrays` of experts `offset` .. `offset + held` from
    an uncut layer's leaves by their published names."""
    sl = slice(offset, offset + held)
    arrays = {"gate_w": params["router_w"],
              "eu": params["experts_up_w"][sl],
              "ed": params["experts_down_w"][sl]}
    if "experts_gate_w" in params:
        arrays["eg"] = params["experts_gate_w"][sl]
    if bias and "router_bias" in params:
        arrays["bias"] = params["router_bias"]
    return arrays


def uncut_expert_layer(total, d=16, f=8, n=96, seed=0):
    """(tokens [n, d], the leaves of an uncut expert layer of `total` gated
    experts of width f with a selection bias of zeros and a shared expert,
    by their published names), seeded."""
    rng = np.random.RandomState(seed)
    mat = lambda *shape: rng.randn(*shape).astype(np.float32) * 0.2  # noqa: E731
    params = {
        "router_w": mat(d, total) * 1.5, "router_bias": np.zeros(
            total, np.float32), "experts_gate_w": mat(total, d, f),
        "experts_up_w": mat(total, d, f), "experts_down_w": mat(total, f, d),
        "shared_gate_w": mat(d, f), "shared_up_w": mat(d, f),
        "shared_down_w": mat(f, d)}
    return rng.randn(n, d).astype(np.float32), params


def mixer_program(build, cfg, x, params, pre):
    """One share's mixer `build(x, cfg, pre)` through a Program from the
    leaves `params`: its output on `x`."""
    reset_programs(0)
    xv = layers.data(name="x", shape=list(x.shape[1:]), dtype="float32")
    out = build(xv, cfg, pre)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    for name, value in params.items():
        assert tuple(fluid.global_scope().find(name).shape) == tuple(
            value.shape), name
        fluid.global_scope().set(name, jnp.asarray(value))
    return np.asarray(exe.run(feed={"x": x}, fetch_list=[out])[0])


# ---------------------------------------------------------------------------
# digests: a trace held to the tree before
# ---------------------------------------------------------------------------


def sha256(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


def cut_source_lines(text: str, files: str = None) -> str:
    """A jaxpr's text with `<file>.py:<line>` cut: of the files whose stems
    match the pattern `files`, or of every file, path and all."""
    if files is None:
        return re.sub(r"[\w/.\-]+\.py:\d+", "F:N", text)
    return re.sub(rf"({files})\.py:\d+", r"\1.py:N", text)


def program_digest() -> str:
    """sha256 of the default main and startup Programs as built: every
    block's variables (name, shape, dtype, flags) and ops (type, slots,
    attributes) in order."""
    desc = [p.to_desc() for p in (fluid.default_main_program(),
                                  fluid.default_startup_program())]
    return sha256(json.dumps(desc, sort_keys=True, default=repr))


def tiny_step_digests(build):
    """(sha256 of a builder's tiny float32 train step of k = 2 as a jaxpr,
    source lines cut; `program_digest` of what `build` built, before the
    optimizer's ops). `build()` returns (loss, feed)."""
    reset_programs(0)
    loss, feed = build()
    built = program_digest()
    paddle.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    text = cut_source_lines(str(exe.step_jaxpr(feed, [loss], k=2)))
    return sha256(text), built


def causal_lm(module, cfg):
    """A `build` for `tiny_step_digests`: `module`'s causal LM at `cfg`."""
    def build():
        _, loss, _ = module.build_causal_lm_program(cfg)
        return loss, {"tokens": np.zeros((2, 2, cfg.seq_len), np.int64)}
    return build


def routed_moe_jaxpr(gate, bias, held, total, attrs, n, d=128, f=256):
    """The text of `routed_moe`'s forward and grad rule as one jaxpr at
    bf16 experts [held, d, f], source lines of `ops/moe.py` and the grouped
    matmuls cut. The caller patches `grouped_matmul.interpret_mode` to
    False: the kernels as the chip gets them."""
    opdef = registry.get("routed_moe")
    names = ["X", "GateW"] + ["ExpertGate"] * gate + ["ExpertUp",
                                                      "ExpertDown"]

    def step(x, wg, sb, eg, eu, ed, g):
        ctx = registry.LowerCtx(rng_key=None)
        ins = {"X": [x], "GateW": [wg], "ExpertUp": [eu], "ExpertDown": [ed]}
        if gate:
            ins["ExpertGate"] = [eg]
        if bias:
            ins["SelectBias"] = [sb]
        outs = opdef.lower(ctx, ins, attrs)
        grads = opdef.grad(ctx, ins, attrs,
                           {s: outs[s] for s in opdef.residual_slots
                            if s in outs}, {"Out": [g]})
        return outs["Out"][0], [grads[s][0] for s in names]

    bf, sd = jnp.bfloat16, jax.ShapeDtypeStruct
    text = str(jax.make_jaxpr(step)(
        sd((n, d), jnp.float32), sd((d, total), jnp.float32),
        sd((total,), jnp.float32), sd((held, d, f), bf),
        sd((held, d, f), bf), sd((held, f, d), bf), sd((n, d), bf)))
    return cut_source_lines(text, "moe|grouped_matmul")
