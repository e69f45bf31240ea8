"""Executor.run_steps: the device-side k-step scan training loop.

Counterpart of running the reference's trainer loop k times; one dispatch
here (see executor.py _run_block_multistep)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.framework import errors


def _build(seed=0):
    np.random.seed(seed)
    x = layers.data(name="x", shape=[6], dtype="float32")
    y = layers.data(name="y", shape=[1], dtype="float32")
    h = layers.fc(x, 8, act="tanh")
    pred = layers.fc(h, 1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    paddle.optimizer.Adam(learning_rate=1e-2).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    return exe, loss


def test_run_steps_matches_sequential_runs():
    rng = np.random.RandomState(0)
    w = rng.randn(6, 1).astype(np.float32)
    xs = rng.randn(5, 16, 6).astype(np.float32)
    ys = np.einsum("kbf,fo->kbo", xs, w).astype(np.float32)

    exe, loss = _build()
    seq_losses = []
    for i in range(5):
        out, = exe.run(feed={"x": xs[i], "y": ys[i]}, fetch_list=[loss])
        seq_losses.append(float(out))
    seq_params = {p.name: np.asarray(fluid.global_scope().find(p.name))
                  for p in fluid.default_main_program().all_parameters()}

    # fresh identical model, one dispatch of 5 steps
    from paddle_tpu.framework import program as pm, scope as sm, unique_name
    pm._main_program = pm.Program()
    pm._startup_program = pm.Program()
    sm._reset_global_scope()
    unique_name.switch()
    exe2, loss2 = _build()
    stacked, = exe2.run_steps(5, feed={"x": xs, "y": ys},
                              fetch_list=[loss2])
    np.testing.assert_allclose(stacked.reshape(-1), seq_losses, rtol=2e-4,
                               atol=1e-5)
    for p in fluid.default_main_program().all_parameters():
        np.testing.assert_allclose(
            np.asarray(fluid.global_scope().find(p.name)),
            seq_params[p.name], rtol=2e-4, atol=1e-5)


def test_run_steps_broadcast_feed_and_training_progress():
    exe, loss = _build(seed=1)
    rng = np.random.RandomState(1)
    xb = rng.randn(32, 6).astype(np.float32)
    yb = (xb.sum(1, keepdims=True)).astype(np.float32)
    first, = exe.run_steps(20, feed={"x": xb, "y": yb}, fetch_list=[loss])
    assert first.shape[0] == 20
    assert first[-1] < first[0] * 0.7  # trained across the scanned steps
    # state persisted: a second call continues improving
    second, = exe.run_steps(20, feed={"x": xb, "y": yb}, fetch_list=[loss])
    assert second[-1] < first[-1] * 1.05


def test_run_steps_dropout_varies_per_step():
    np.random.seed(0)
    x = layers.data(name="x", shape=[64], dtype="float32")
    d = layers.dropout(x, dropout_prob=0.5)
    s = layers.reduce_sum(d)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    out, = exe.run_steps(4, feed={"x": np.ones((8, 64), np.float32)},
                         fetch_list=[s])
    assert len(set(np.round(np.asarray(out).reshape(-1), 3))) > 1, \
        "each scanned step must draw fresh dropout"


def test_run_steps_k1_matches_run():
    """k=1 is a legal degenerate scan (feeds still carry the [1] axis)."""
    rng = np.random.RandomState(3)
    xb = rng.randn(16, 6).astype(np.float32)
    yb = xb.sum(1, keepdims=True).astype(np.float32)

    exe, loss = _build(seed=3)
    ref, = exe.run(feed={"x": xb, "y": yb}, fetch_list=[loss])
    ref_params = {p.name: np.asarray(fluid.global_scope().find(p.name))
                  for p in fluid.default_main_program().all_parameters()}

    from paddle_tpu.framework import program as pm, scope as sm, unique_name
    pm._main_program = pm.Program()
    pm._startup_program = pm.Program()
    sm._reset_global_scope()
    unique_name.switch()
    exe2, loss2 = _build(seed=3)
    stacked, = exe2.run_steps(1, feed={"x": xb, "y": yb},
                              fetch_list=[loss2])
    assert stacked.shape[0] == 1
    np.testing.assert_allclose(stacked[0], ref, rtol=2e-4, atol=1e-5)
    for p in fluid.default_main_program().all_parameters():
        np.testing.assert_allclose(
            np.asarray(fluid.global_scope().find(p.name)),
            ref_params[p.name], rtol=2e-4, atol=1e-5)


def test_run_steps_rejects_k_below_one():
    exe, loss = _build(seed=4)
    with pytest.raises(errors.InvalidArgumentError):
        exe.run_steps(0, feed={}, fetch_list=[loss])


def test_run_steps_rejects_ps_and_pipeline():
    exe, loss = _build(seed=2)
    prog = fluid.default_main_program()
    prog._ps_hooks = [object()]
    with pytest.raises(errors.UnimplementedError):
        exe.run_steps(2, feed={}, fetch_list=[loss])
    prog._ps_hooks = []
    prog._microbatch_k = 4
    with pytest.raises(errors.UnimplementedError):
        exe.run_steps(2, feed={}, fetch_list=[loss])
    prog._microbatch_k = 0


# --------------------------------------------------------------------------
# one dispatch path: run() is Executor._dispatch with k=None, run_steps(k)
# the same body with k; compiled_hlo / step_jaxpr resolve a call to its
# block through the same Executor._resolve_call and _block_for
# --------------------------------------------------------------------------

def _batch(seed=5):
    rng = np.random.RandomState(seed)
    xb = rng.randn(16, 6).astype(np.float32)
    return {"x": xb, "y": xb.sum(1, keepdims=True).astype(np.float32)}


def _dispatch(exe, k, feed, loss, **kw):
    if k is None:
        return exe.run(feed=feed, fetch_list=[loss], **kw)
    return exe.run_steps(k, feed=feed, fetch_list=[loss], **kw)


def _cache_counts():
    from paddle_tpu.observability import metrics
    return (metrics.get("executor.compile_cache_misses"),
            metrics.get("executor.compile_cache_hits"))


@pytest.mark.parametrize("k", [None, 1, 4])
def test_inspection_after_a_dispatch_hits_its_cache_entry(k):
    exe, loss = _build(seed=5)
    feed = _batch()
    _dispatch(exe, k, feed, loss)
    n_blocks = len(exe._cache)
    misses, hits = _cache_counts()
    assert "ENTRY" in exe.compiled_hlo(feed, [loss], k=k)
    assert exe.step_jaxpr(feed, [loss], k=k).jaxpr.eqns
    assert exe.compiled_memory_analysis(feed, [loss], k=k) is not None
    assert _cache_counts() == (misses, hits + 3)
    assert len(exe._cache) == n_blocks


@pytest.mark.parametrize("k", [None, 1, 4])
def test_inspection_before_a_dispatch_builds_the_entry_it_then_hits(k):
    exe, loss = _build(seed=5)
    feed = _batch()
    misses, hits = _cache_counts()
    jaxpr = str(exe.step_jaxpr(feed, [loss], k=k))
    assert _cache_counts() == (misses + 1, hits)
    n_blocks = len(exe._cache)
    _dispatch(exe, k, feed, loss)
    assert len(exe._cache) == n_blocks
    assert _cache_counts() == (misses + 1, hits + 1)
    # and the dispatch left the entry as inspection made it
    assert str(exe.step_jaxpr(feed, [loss], k=k)) == jaxpr


@pytest.mark.parametrize("k", [None, 1, 4])
def test_a_staged_window_waits_for_its_dispatch_of_either_shape(k):
    from paddle_tpu import monitor
    exe, loss = _build(seed=5)
    feed = _batch()
    ref, = _dispatch(exe, k, feed, loss)         # warm, and the shape
    monitor.stat_reset("executor.dispatch_queue_depth")
    exe.stage(feed, k=k)
    assert monitor.stat_get("executor.dispatch_queue_depth") == 1
    # inspection resolves the same call and leaves the window queued
    exe.compiled_hlo(feed, [loss], k=k)
    assert monitor.stat_get("executor.dispatch_queue_depth") == 1
    # the other shape's dispatch of the same feed objects is not its owner
    other = 2 if k is None else None
    _dispatch(exe, other, feed, loss)
    assert monitor.stat_get("executor.dispatch_queue_depth") == 1
    h2d = monitor.stat_get("executor.h2d_ms")
    out, = _dispatch(exe, k, feed, loss)
    assert monitor.stat_get("executor.dispatch_queue_depth") == 0
    assert monitor.stat_get("executor.h2d_ms") == h2d
    assert np.asarray(out).shape == np.asarray(ref).shape


def _pp2(prog):
    import jax
    from paddle_tpu.parallel import DistConfig, attach, build_mesh
    attach(prog, DistConfig(mesh=build_mesh(pp=2,
                                            devices=jax.devices()[:2])))


class _NoWindowHook:
    grad_name = "nope"


class _GeoHook(_NoWindowHook):
    geo_k = 4

    def pre_multi(self, feed):
        return {}


_REFUSED_BY_RUN_STEPS = {
    "ps_hook_without_window": lambda p: setattr(p, "_ps_hooks",
                                                [_NoWindowHook()]),
    "geo_sgd_hook": lambda p: setattr(p, "_ps_hooks", [_GeoHook()]),
    "localsgd": lambda p: setattr(p, "_localsgd_k", 4),
    "microbatched": lambda p: setattr(p, "_microbatch_k", 4),
    "pp_mesh": _pp2,
}


@pytest.mark.parametrize("case", sorted(_REFUSED_BY_RUN_STEPS))
def test_run_steps_refusals_keep_their_type_and_dispatch_nothing(case):
    exe, loss = _build(seed=6)
    _REFUSED_BY_RUN_STEPS[case](fluid.default_main_program())
    steps, blocks = exe._step_counter, len(exe._cache)
    with pytest.raises(errors.UnimplementedError, match="run_steps"):
        exe.run_steps(2, feed=_batch(), fetch_list=[loss])
    assert (exe._step_counter, len(exe._cache)) == (steps, blocks)


@pytest.mark.parametrize("bad_k", [0, -1, 1.5, "2", None])
def test_run_steps_refuses_a_k_that_is_no_positive_integer(bad_k):
    exe, loss = _build(seed=6)
    with pytest.raises(errors.InvalidArgumentError, match="integer k >= 1"):
        exe.run_steps(bad_k, feed=_batch(), fetch_list=[loss])


_REFUSED_BY_INSPECTION = {
    "ps_hooks": (lambda p: setattr(p, "_ps_hooks", [_GeoHook()]), None,
                 errors.UnimplementedError),
    "localsgd": (lambda p: setattr(p, "_localsgd_k", 4), None,
                 errors.UnimplementedError),
    "pp_mesh": (_pp2, None, errors.UnimplementedError),
    "microbatched_with_k": (lambda p: setattr(p, "_microbatch_k", 4), 2,
                            errors.UnimplementedError),
    "k_zero": (lambda p: None, 0, errors.InvalidArgumentError),
    "k_bool": (lambda p: None, True, errors.InvalidArgumentError),
    "k_float": (lambda p: None, 2.0, errors.InvalidArgumentError),
}


@pytest.mark.parametrize("case", sorted(_REFUSED_BY_INSPECTION))
def test_inspection_refusals_keep_their_types(case):
    mark, k, error = _REFUSED_BY_INSPECTION[case]
    exe, loss = _build(seed=6)
    mark(fluid.default_main_program())
    blocks = len(exe._cache)
    for entry in (exe.compiled_hlo, exe.step_jaxpr,
                  exe.compiled_memory_analysis, exe.annotate_step_cost):
        with pytest.raises(error, match="compiled_hlo"):
            entry(_batch(), [loss], k=k)
    assert len(exe._cache) == blocks


@pytest.mark.parametrize("k", [None, 4])
def test_an_unknown_fetch_target_is_refused_by_every_entry_point(k):
    exe, loss = _build(seed=6)
    for call in (lambda: _dispatch(exe, k, _batch(), "no_such_var"),
                 lambda: exe.compiled_hlo(_batch(), ["no_such_var"], k=k),
                 lambda: exe.step_jaxpr(_batch(), ["no_such_var"], k=k)):
        with pytest.raises(errors.NotFoundError, match="no_such_var"):
            call()


@pytest.mark.parametrize("k", [None, 4])
def test_check_nan_inf_names_the_variable_in_either_shape(k):
    """FLAGS_check_nan_inf scans fetches and written state in the one
    dispatch body: run_steps used to ignore the flag without a word."""
    from paddle_tpu.flags import set_flags
    exe, loss = _build(seed=7)
    feed = _batch()
    if k is not None:                            # NaN in step 3 of 4 only
        feed = {n: np.stack([v] * k) for n, v in feed.items()}
        feed["x"][2, 0, 0] = np.nan
    else:
        feed["x"][0, 0] = np.nan
    set_flags({"FLAGS_check_nan_inf": True})
    try:
        with pytest.raises(FloatingPointError, match=loss.name):
            _dispatch(exe, k, feed, loss)
    finally:
        set_flags({"FLAGS_check_nan_inf": False})


def test_flags_benchmark_is_a_documented_no_op(capsys):
    """Its printed time is the `executor.launch` span's now; the dispatch
    body reads one flag less and syncs for nobody."""
    from paddle_tpu.flags import _DEFS, set_flags
    exe, loss = _build(seed=8)
    capsys.readouterr()
    set_flags({"FLAGS_benchmark": True})
    try:
        exe.run(feed=_batch(), fetch_list=[loss])
        exe.run_steps(2, feed=_batch(), fetch_list=[loss])
    finally:
        set_flags({"FLAGS_benchmark": False})
    assert "[benchmark]" not in capsys.readouterr().out
    assert _DEFS["FLAGS_benchmark"][1].startswith("no-op")
