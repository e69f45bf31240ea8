"""What a recomputed segment keeps beside its boundary
(`ops/registry.py` `keep_under_recompute`, `parallel/transforms.py`
`__segment__` / `__layer_scan__`): a selection, its target, the flash
output with one lane of its logsumexp, an expert layer's route. The values
ops mark are saved by the segment's `jax.checkpoint`, read by its backward
and made once a step; every result is the bare checkpoint's bit for bit,
and the unrecomputed program's at the first step. Over every builder that a benchmark
cell trains under `strategy.recompute`, at its tiny preset on the CPU
(`keye_flash`: the flash route under the Pallas interpreter, where the
attention's own marks are).
"""
import collections
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax._src.ad_checkpoint import saved_residuals

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import causal_lm_harness as harness  # noqa: E402
import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.fluid as fluid  # noqa: E402
from paddle_tpu.framework import executor  # noqa: E402
from paddle_tpu.models import bert, keye, lfm2, ling, nemotron_h  # noqa: E402
from paddle_tpu.observability import metrics  # noqa: E402
from paddle_tpu.ops import attention, registry  # noqa: E402
from paddle_tpu.parallel import transforms  # noqa: E402
from paddle_tpu.parallel.transforms import apply_layer_scan  # noqa: E402
from paddle_tpu.testing import reset_programs  # noqa: E402

_KEPT = ("recompute.kept_values", "recompute.kept_bytes")


def _keye_flash():
    """Two layers whose attention and indexer take the kernels' shapes:
    rows of 128, heads 64 wide."""
    cfg = keye.KeyeConfig.tiny()
    cfg.seq_len, cfg.head_dim, cfg.index_topk = 128, 64, 40
    cfg.indexer_head_dim = 64
    cfg.num_attention_heads, cfg.num_key_value_heads = 6, 2
    cfg.hidden_size, cfg.moe_intermediate_size = 128, 256
    cfg.mrope_section = (8, 12, 12)
    return cfg


# builder -> (module, preset, flash route, expert layers, sparse layers)
_BUILDERS = {
    "keye": (keye, keye.KeyeConfig.tiny, False, 2, 2),
    "keye_flash": (keye, _keye_flash, True, 2, 2),
    "nemotron_h": (nemotron_h, nemotron_h.NemotronHConfig.tiny, False, 4, 0),
    "nemotron_h_latent": (
        nemotron_h, nemotron_h.NemotronHConfig.tiny_latent_share, False, 4,
        0),
    "ling": (ling, ling.LingConfig.tiny, False, 3, 0),
    "lfm2": (lfm2, lfm2.Lfm2Config.tiny, False, 3, 0),
}


@pytest.fixture(params=sorted(_BUILDERS))
def builder(request, monkeypatch):
    module, preset, flash, experts, sparse = _BUILDERS[request.param]
    if flash:
        monkeypatch.setattr(attention, "_use_pallas",
                            lambda q: q.shape[2] % 128 == 0)
    return module, preset, flash, experts, sparse


def _bare(monkeypatch):
    """The policy off: a segment's checkpoint saves its inputs alone."""
    monkeypatch.setattr(registry, "checkpointed", jax.checkpoint)


def _step(module, preset, recompute):
    """(executor, loss, ids) of the AMP train step, as the cells run it."""
    return harness.amp_step(module, preset(), recompute)


def _two_steps(module, preset, recompute):
    """Everything two steps leave: the losses, each layer's selection
    where the model has one, and the scope (parameters, Adam's moments)."""
    exe, loss, ids = _step(module, preset, recompute)
    fetch = [loss] + list(getattr(loss, "_selections", []))
    out = exe.run_steps(2, feed={"tokens": ids}, fetch_list=fetch)
    scope = fluid.global_scope()
    state = {n: np.asarray(scope.find(n)) for n in scope.local_names()
             if isinstance(getattr(scope.find(n), "dtype", None), np.dtype)}
    return [np.asarray(v) for v in out], state


def _same(got, want):
    got_out, got_state = got
    want_out, want_state = want
    for g, w in zip(got_out, want_out):
        np.testing.assert_array_equal(g, w)
    assert sorted(got_state) == sorted(want_state)
    for name, value in want_state.items():
        np.testing.assert_array_equal(got_state[name], value, err_msg=name)


def test_results_are_the_bare_checkpoints_and_the_plain_programs(
        builder, monkeypatch):
    """(a), (d): two steps under `strategy.recompute` with the kept values
    leave the losses, the fetched selections, every parameter and both of
    Adam's moments that the same steps leave with the policy off (a bare
    `jax.checkpoint`), bit for bit: a value kept is the value the
    recomputed forward would have made. Against the program that does not
    recompute (its backward is the ops' grad rules on residuals, another
    program to XLA, whose bf16 gradients round elsewhere) the first step's
    loss and selections are equal and the second step's loss agrees to
    that rounding."""
    module, preset = builder[:2]
    kept = _two_steps(module, preset, True)
    assert np.isfinite(kept[0][0]).all()
    plain = _two_steps(module, preset, False)
    for g, w in zip(kept[0], plain[0]):
        np.testing.assert_array_equal(g[0], w[0])
    np.testing.assert_allclose(kept[0][0], plain[0][0], rtol=1e-3)
    _bare(monkeypatch)
    _same(kept, _two_steps(module, preset, True))


# what makes a kept value, as (primitive, kernel name or scope)
def _producers(jaxpr, found, backward=False):
    """Count, over a jaxpr and the jaxprs its equations hold, the
    equations that make a kept value, apart for those inside a
    differentiated checkpoint (the backward: what a step recomputes)."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        inside = backward or (name == "remat2"
                              and eqn.params["differentiated"])
        scope = str(eqn.source_info.name_stack)
        what = None
        if name == "pallas_call":
            kernel = eqn.params["name"]
            if kernel in ("flash_attention_fwd", "selected_probs_sum"):
                what = kernel
        elif name == "top_k" and "moe.route" in scope:
            what = "route top_k"
        elif name == "sort" and "moe.route" in scope:
            what = "route sort"
        elif name == "jit" and eqn.params["name"] == "cumsum" \
                and any(v.aval.ndim == 3 for v in eqn.outvars):
            what = "selection"      # `select_topk`'s running count [B, R, S]
        elif name == "name":
            what = "marks"
        if what:
            found[(what, inside)] += 1
        if name == "pallas_call":
            continue        # a kernel's own loops are not the program's
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _producers(sub, found, inside)
    return found


def _census(module, preset):
    exe, loss, ids = _step(module, preset, True)
    jaxpr, rise = harness.counter_rise(
        lambda: exe.step_jaxpr({"tokens": ids}, [loss], k=2), _KEPT)
    return _producers(jaxpr.jaxpr, collections.Counter()), rise


def test_the_backward_makes_no_kept_value_again(builder, monkeypatch):
    """(b), (d): in the step's jaxpr the forward is there once, and the
    differentiated checkpoints (the backward) hold no `top_k`, no selection
    loop, no target kernel and no flash forward; of the route's sorts they
    hold the one that is the backward's own (`_sort_slots`'s gradient by
    `_unsort`). With the policy off the same jaxpr holds each of them once
    more a layer, inside the backward: what the parent's tree compiled."""
    module, preset, flash, experts, sparse = builder
    kept, _ = _census(module, preset)
    _bare(monkeypatch)
    bare, _ = _census(module, preset)
    # forward: the route's top_k (group-limited routers take two more for
    # the groups) and its three sorts, the selection, the two kernels
    per_layer = {"route top_k": experts, "route sort": experts,
                 "selection": sparse,
                 "flash_attention_fwd": sparse if flash else 0,
                 "selected_probs_sum": sparse if flash else 0}
    for what, layers in per_layer.items():
        assert kept[(what, False)] == bare[(what, False)] >= layers, what
        assert (bare[(what, False)] > 0) == (layers > 0), what
        if what == "route sort":
            assert bare[(what, True)] - kept[(what, True)] >= layers, what
        else:
            assert kept[(what, True)] == 0, what
            assert bare[(what, True)] >= layers, what
    assert kept[("marks", False)] > 0


def _route_bytes(cfg_tokens, top_k, held):
    """idx [N, k], sizes [E_held], order and w_sorted [k N], inv [rows]."""
    rows = min(top_k, held) * cfg_tokens
    return 4 * (cfg_tokens * top_k + held + 2 * top_k * cfg_tokens + rows)


def test_a_trace_counts_what_it_keeps(builder):
    """(e): `recompute.kept_values` rises once a marked value a trace of
    the recomputed step, five a routed expert layer (the chosen experts,
    the experts' sizes, the sort with the weights it carried, the rows the
    combine gathers), the selection a sparse layer and, on the flash
    route, its target, the flash output and one lane of the logsumexp;
    `recompute.kept_bytes` reads their bytes. The step that does not
    recompute keeps nothing."""
    module, preset, flash, experts, sparse = builder
    exe, loss, ids = _step(module, preset, False)
    _, rise = harness.counter_rise(
        lambda: exe.step_jaxpr({"tokens": ids}, [loss], k=2), _KEPT)
    assert rise == (0, 0)
    metrics.reset("recompute.kept_bytes")
    _, rise = _census(module, preset)
    assert rise[0] == 5 * experts + (4 if flash else 1) * sparse
    if module is keye:
        cfg = preset()
        s, heads = cfg.seq_len, cfg.num_attention_heads
        per_layer = _route_bytes(s, cfg.num_experts_per_tok,
                                 cfg.experts_held or cfg.num_experts) + s * s
        if flash:
            per_layer += 4 * s * s + 2 * heads * s * cfg.head_dim \
                + 4 * heads * s
        assert rise[1] == 2 * per_layer
    else:
        assert rise[1] > 0


def _layer_segments(program):
    return [op for op in program.global_block().ops
            if op.type == "__segment__" and op.attrs["remat"]
            and any(d["type"] in ("routed_moe", "sparse_index")
                    for d in op.attrs["sub_ops"])]


def test_a_segment_saves_its_inputs_and_the_marked_values(builder):
    """(c): JAX's `saved_residuals` of a layer's segment under the policy
    lists the segment's inputs, constants, and the values marked by
    `keep_under_recompute` (a float one behind the `reduce_precision` that
    `jax.checkpoint` puts on a float residual's producer), as many as the
    layer's ops mark. Beside them only integer index vectors: the operand
    of a gather that is linear in its other operand (the expert layer's
    dispatch reads `order % N`), which JAX keeps under any policy."""
    module, preset, flash, experts, sparse = builder
    _step(module, preset, True)
    program = fluid.default_main_program()
    block = program.global_block()
    segments = _layer_segments(program)
    assert segments
    op = segments[-1]
    rng = np.random.RandomState(0)

    def value(name):
        var = block.find_var_recursive(name)
        found = fluid.global_scope().find(name)
        if found is not None:
            return jnp.asarray(found)
        shape = tuple(1 if d in (-1, None) else d for d in var.shape)
        if jnp.issubdtype(var.dtype, jnp.floating):
            return jnp.asarray(rng.randn(*shape) * 0.1, var.dtype)
        return jnp.zeros(shape, var.dtype)

    xs = [value(n) for n in op.attrs["in_names"]]
    ctx = registry.LowerCtx(rng_key=jax.random.key(0))
    executor._lowering_programs.append(program)
    try:
        with registry.recomputed(count=False):
            saved = saved_residuals(
                registry.checkpointed(transforms._segment_fn(ctx, op.attrs)),
                xs)
    finally:
        executor._lowering_programs.pop()
    marked = [why for _, why in saved if why.startswith(
        ("named 'kept_under_recompute'", "output of reduce_precision"))]
    others = [(aval, why) for aval, why in saved if why not in marked
              and not why.startswith(("from the argument", "from a constant"))]
    layer_has = {d["type"] for d in op.attrs["sub_ops"]}
    assert len(marked) == 5 * ("routed_moe" in layer_has) \
        + (4 if flash else 1) * ("sparse_index" in layer_has)
    assert all(aval.ndim == 1 and jnp.issubdtype(aval.dtype, jnp.integer)
               for aval, _ in others), others
    assert len(others) <= 1


# ---------------------------------------------------------------------------
# (f) a rolled stack of layers whose scan body is a checkpoint
# ---------------------------------------------------------------------------

def _rolled_bert(num_layers=3):
    reset_programs(0)
    cfg = bert.BertConfig(vocab_size=256, hidden_size=128,
                          num_layers=num_layers, num_heads=2,
                          intermediate_size=64, max_position=128,
                          seq_len=128, hidden_dropout=0.0,
                          attention_dropout=0.0)
    _, _, loss = bert.build_pretrain_program(cfg)
    consumed = apply_layer_scan(
        fluid.default_main_program(), loss._layer_checkpoints, remat=True,
        startup_program=fluid.default_startup_program())
    assert consumed == loss._layer_checkpoints[:-1]
    paddle.optimizer.Adam(learning_rate=0.01).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(1)
    feed = {"input_ids": rng.randint(0, 256, (2, 128)).astype(np.int64),
            "mlm_labels": rng.randint(0, 256, (2, 128, 1)).astype(np.int64)}
    return exe, feed, loss


@pytest.mark.parametrize("layers", [2, 3])
def test_a_layer_scan_with_remat_keeps_the_same_values(layers, monkeypatch):
    """(f): the body of a `__layer_scan__` with `remat` is a checkpoint
    under the same policy: on the flash route a layer's `Out` and one lane
    of its `Lse` are kept, counted once a layer of the scan, and the
    scan's backward body launches no forward kernel; with the policy off
    it launches one. The loss is the same either way."""
    monkeypatch.setattr(attention, "_use_pallas",
                        lambda q: q.shape[2] % 128 == 0)
    rows, heads, s, hd = 2, 2, 128, 64
    losses = []
    for bare in (False, True):
        if bare:
            _bare(monkeypatch)
        exe, feed, loss = _rolled_bert(layers)
        metrics.reset("recompute.kept_bytes")
        jaxpr, rise = harness.counter_rise(
            lambda: exe.step_jaxpr(feed, [loss]), _KEPT)
        found = _producers(jaxpr.jaxpr, collections.Counter())
        assert found[("flash_attention_fwd", True)] == int(bare)
        # (a mark counts whatever the policy then does with it)
        assert rise == (2 * layers,
                        layers * rows * heads * s * (hd * 4 + 4))
        losses.append(np.asarray(exe.run(feed=feed, fetch_list=[loss])[0]))
    assert np.isfinite(losses[0]).all()
    np.testing.assert_array_equal(*losses)
