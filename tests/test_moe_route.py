"""`routed_moe`'s route moves no scalar by a gather or a scatter
(`ops/moe.py`: `_slot_weights` as a one-hot reduce, the inverse permutation
and the sort's payload gradient by `_unsort`), and what it computes is, bit
for bit, what the gathers and scatters it replaced computed: the parent's
formulation is kept here as the oracle."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.fluid as fluid  # noqa: E402
from paddle_tpu.distributed import fleet  # noqa: E402
from paddle_tpu.fluid import layers  # noqa: E402
from paddle_tpu.models import deepseek_v3, ling, mellum, nemotron_h  # noqa: E402
from paddle_tpu.ops import moe  # noqa: E402
from paddle_tpu.parallel import apply_recompute  # noqa: E402
from paddle_tpu.testing import reset_programs  # noqa: E402


# ---------------------------------------------------------------------------
# the oracle: the route as it stood before (commit da67113), scalar by scalar
# ---------------------------------------------------------------------------

def _gather_slot_weights(scores, idx, local, attrs):
    w = jnp.take_along_axis(scores, idx, axis=1)
    if attrs.get("norm_topk", True):
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    w = w * float(attrs.get("routed_scaling", 1.0))
    return jnp.where(local, w, 0.0).T


def _sort_slots_by_jax(eid, w):
    """No rule of its own: JAX's for a sort gathers the payload's tangent
    by `order`, and the transpose is a scatter-add."""
    slots = jnp.arange(eid.shape[0], dtype=jnp.int32)
    _, order, w_sorted = jax.lax.sort((eid, slots, w), num_keys=1,
                                      is_stable=True)
    return order, w_sorted


def _scatter_unsort(order, a_sorted):
    if a_sorted.dtype == jnp.int32:      # the slots' own numbers: `inv`
        return jnp.zeros(order.shape, jnp.int32).at[order].set(
            a_sorted, unique_indices=True)
    # the rule's dw came back by a sort at the parent too
    return jax.lax.sort((order, a_sorted), num_keys=1)[1]


_ORACLE = {"_slot_weights": _gather_slot_weights,
           "_sort_slots": _sort_slots_by_jax, "_unsort": _scatter_unsort}

# the route's shapes, tiny: N = 64 tokens of d = 32, experts 16 wide
_CASES = {
    "sigmoid-bias-norm-f32": dict(total=16, held=8, top_k=3),
    "softmax-no-bias-bf16": dict(total=16, held=8, top_k=4, bias=False,
                                 scoring="softmax", dtype="bfloat16"),
    "groups-4-of-8-f32": dict(total=64, held=8, top_k=8, n_group=8,
                              topk_group=4),
    "top22-of-8-held-latent-bf16": dict(total=64, held=8, top_k=22,
                                        latent=24, gate=False, scaling=5.0,
                                        dtype="bfloat16"),
    "offset-mostly-foreign-f32": dict(total=32, held=4, offset=20, top_k=3,
                                      norm=False),
}
_DEFAULTS = dict(bias=True, norm=True, scaling=2.5, offset=0,
                 scoring="sigmoid", n_group=1, topk_group=1, dtype="float32")
_ROUTES = ("rule", "withheld", "segment")
_RULE_ONLY = ("H", "U", "SortedW", "Order", "Inv")


def _operands(total, held, top_k, latent=None, gate=True, dtype="float32",
              n=64, d=32, f=16, **_):
    rng = np.random.RandomState(total * 100 + top_k)
    d_e = latent or d
    cast = lambda a: jnp.asarray(a, dtype)  # noqa: E731
    feed = {"x": cast(rng.randn(n, d)), "cot": cast(rng.randn(n, d_e))}
    if latent:
        feed["z"] = cast(rng.randn(n, d_e))
    params = {"gate_w": jnp.asarray(rng.randn(d, total) * 0.3, jnp.float32),
              "bias": jnp.asarray(rng.randn(total) * 0.1, jnp.float32),
              "eu": cast(rng.randn(held, d_e, f) * 0.2),
              "ed": cast(rng.randn(held, f, d_e) * 0.2)}
    if gate:
        params["eg"] = cast(rng.randn(held, d_e, f) * 0.2)
    return feed, params


def _route_run(case, route):
    """One `routed_moe` through a Program: what the forward writes and the
    gradients of sum(Out * cot), by the op's grad rule on its residuals
    (`rule`), by the generic `__vjp__` of an op whose residual outputs are
    withheld, or by the `__vjp__` of a recomputed `__segment__`."""
    spec = {**_DEFAULTS, **_CASES[case]}
    feed, params = _operands(**spec)
    reset_programs(0)
    var = {k: layers.data(name=k, shape=[v.shape[1]], dtype=spec["dtype"])
           for k, v in feed.items()}
    for v in var.values():
        v.stop_gradient = False
    var.update({k: layers.create_parameter(list(v.shape), str(v.dtype),
                                           name=k)
                for k, v in params.items()})
    var["bias"].stop_gradient = True
    # an op in front, so that a segment up to `Out` holds two
    out, idx, load = layers.routed_moe(
        layers.scale(var["x"], 1.0), var["gate_w"], var.get("eg"),
        var["eu"], var["ed"], top_k=spec["top_k"],
        select_bias=var["bias"] if spec["bias"] else None,
        routed_scaling=spec["scaling"], norm_topk=spec["norm"],
        experts_total=spec["total"], expert_offset=spec["offset"],
        scoring=spec["scoring"], n_group=spec["n_group"],
        topk_group=spec["topk_group"], expert_input=var.get("z"))
    loss = layers.reduce_sum(layers.elementwise_mul(out, var["cot"]))
    prog = fluid.default_main_program()
    op, = (o for o in prog.global_block().ops if o.type == "routed_moe")
    fetch = {"Out": out, "TopIdx": idx, "ExpertLoad": load}
    if route == "withheld":
        for slot in _RULE_ONLY:
            op.outputs.pop(slot, None)
    else:
        fetch.update({s: op.outputs[s][0] for s in ("Order", "Inv",
                                                   "SortedW")})
    if route == "segment":
        apply_recompute(prog, [out.name])
        assert [o.type for o in prog.global_block().ops][0] == "__segment__"
    wrt = [k for k in ("x", "z", "gate_w", "eg", "eu", "ed") if k in var]
    grads = fluid.gradients(loss, [var[k] for k in wrt])
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    for k, v in params.items():
        fluid.global_scope().set(k, v)
    got = exe.run(feed=feed, fetch_list=list(fetch.values()) + grads)
    names = list(fetch) + ["d " + k for k in wrt]
    return {k: np.asarray(v) for k, v in zip(names, got)}


@pytest.mark.parametrize("route", _ROUTES)
@pytest.mark.parametrize("case", sorted(_CASES))
def test_route_equals_the_gather_form_bit_for_bit(case, route, monkeypatch):
    """Every sum of the one-hot reduce has one term that is not 0, forward
    and transposed, and a sort by a permutation is its scatter: the op's
    outputs, residuals and gradients are the gather form's, on each
    gradient route."""
    got = _route_run(case, route)
    for name, fn in _ORACLE.items():
        monkeypatch.setattr(moe, name, fn)
    want = _route_run(case, route)
    assert sorted(got) == sorted(want)
    assert {"d x", "d gate_w"} <= set(got)
    for name in want:
        assert got[name].dtype == want[name].dtype, name
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)
    spec = _CASES[case]
    n = got["TopIdx"].shape[0]
    assert got["TopIdx"].shape == (n, spec["top_k"])
    assert np.abs(got["d gate_w"]).max() > 0 and np.abs(got["d x"]).max() > 0
    local = got["ExpertLoad"].sum() / (n * spec["top_k"])
    if "foreign" in case:
        assert 0 < local < 0.3
    if route != "withheld":
        rows = min(spec["top_k"], spec["held"]) * n
        assert got["Inv"].shape == (rows,)
        assert sorted(got["Order"]) == list(range(n * spec["top_k"]))


# ---------------------------------------------------------------------------
# census: a train step of every builder that has an expert layer
# ---------------------------------------------------------------------------

# (module, preset, its expert layers, row gathers a layer under recompute)
_BUILDERS = {
    "deepseek_v3": (deepseek_v3, deepseek_v3.DeepseekV3Config.tiny, 2, 6),
    "mellum": (mellum, mellum.MellumConfig.tiny, 4, 6),
    "nemotron_h": (nemotron_h, nemotron_h.NemotronHConfig.tiny, 4, 6),
    "nemotron_h_latent": (nemotron_h,
                          nemotron_h.NemotronHConfig.tiny_latent_share, 4, 7),
    "ling": (ling, ling.LingConfig.tiny, 3, 6),
}


def _train_step_jaxpr(builder, recompute):
    module, tiny = _BUILDERS[builder][:2]
    reset_programs(0)
    cfg = tiny()
    _, loss, _ = module.build_causal_lm_program(cfg)
    fleet.init(is_collective=True)
    strategy = fleet.DistributedStrategy()
    strategy.amp = True
    if recompute:
        strategy.recompute = True
        strategy.recompute_configs = {
            "checkpoints": list(loss._layer_checkpoints)}
    fleet.distributed_optimizer(paddle.optimizer.Adam(1e-3),
                                strategy).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (2, 1, cfg.seq_len)).astype(np.int64)
    return exe.step_jaxpr({"tokens": ids}, [loss], k=2)


def _moves(jaxpr, found):
    """Every gather and scatter equation of a jaxpr and of the jaxprs its
    equations hold, as (primitive, name stack, moves scalars)."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "gather":
            scalars = all(s == 1 for s in eqn.params["slice_sizes"])
        elif name.startswith("scatter"):
            scalars = not eqn.params["dimension_numbers"].update_window_dims
        else:
            scalars = None
        if scalars is not None:
            found.append((name, str(eqn.source_info.name_stack), scalars))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _moves(sub, found)
    return found


@pytest.mark.parametrize("recompute", [False, True],
                         ids=["rule", "recomputed"])
@pytest.mark.parametrize("builder", sorted(_BUILDERS))
def test_train_step_census_no_scalar_gather_or_scatter_in_the_route(
        builder, recompute):
    """In a trace of the AMP train step no gather or scatter under one of
    the expert layer's scopes (`moe.route`, `.dispatch`, `.experts`,
    `.combine`, forward, recomputed or transposed) moves scalars, by the
    grad rule on residuals and by JAX's transpose of a recomputed segment.
    What they do move is rows, `[1, d]` slices: by the rule 2 forward (the
    dispatch's and the combine's) and 3 backward a layer; under
    recomputation the forward's two are its `jvp`'s (a segment is lowered
    once and differentiates itself there), and the rematerialised copy
    keeps the dispatch's (the combine's too where the buffer is bounded and
    the gather fills): 6 or 7 a layer in the jaxpr."""
    # the four scopes by name: the op's own `moe.io` also holds
    # `_whole_buffer`'s one-element update of the [E_held] sizes
    moves = [m for m in _moves(_train_step_jaxpr(builder, recompute).jaxpr,
                               [])
             if any("moe." + part in m[1] for part in
                    ("route", "dispatch", "experts", "combine", "shared",
                     "latent"))]
    assert not [m for m in moves if m[2]], moves
    assert {m[0] for m in moves} == {"gather"}
    expert_layers, recomputed = _BUILDERS[builder][2:]
    assert len(moves) == (recomputed if recompute else 5) * expert_layers
    assert any("moe.dispatch" in m[1] for m in moves)
    assert any("moe.combine" in m[1] for m in moves)
