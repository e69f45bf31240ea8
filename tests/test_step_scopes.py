"""Every instruction of a compiled step carries a phase and a layer: the
executor's `phase.*` scopes, JAX's mark for recomputed work, the builders'
`program.name_scope`s, the catalogue they are all listed in
(`paddle_tpu/observability/scopes.py`) and its `classify`."""
import ast
import importlib
import os
import re

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.fluid as fluid
from paddle_tpu.distributed import fleet
from paddle_tpu.observability import scopes
from paddle_tpu.testing import reset_programs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEGMENT = "jit(step)/phase.bwd/transpose(jvp(phase.fwd))/jvp()/checkpoint/"


@pytest.mark.parametrize("op_name, want", [
    # the three forms of the issue: a segment's backward, its forward run
    # again, the update
    (SEGMENT + "moe.experts/mul", ("bwd", "moe.experts")),
    (SEGMENT + "rematted_computation/moe.experts/dot_general",
     ("recompute", "moe.experts")),
    ("jit(step)/phase.opt/sub", ("opt", "none")),
    ("jit(step)/phase.opt/optimizer.adam/mul", ("opt", "optimizer.*")),
    # a differentiated forward: the layer's name inside the transform
    ("jit(step)/phase.fwd/jvp(moe.experts)/dot_general",
     ("fwd", "moe.experts")),
    # an inner scope replaces the op's own, a k-step loop stands outside
    ("jit(step)/while/body/phase.fwd/moe.io/moe.route/top_k",
     ("fwd", "moe.route")),
    ("jit(step)/phase.bwd/attn.proj/transpose(jvp())/dot_general",
     ("bwd", "attn.proj")),
    # a forward phase that only a transform wraps is no phase
    ("jit(f)/transpose(jvp(phase.fwd))/mul", ("none", "none")),
    ("jit(step)/convert_element_type", ("none", "none")),
    ("jit(step)/phase.fwd/no.such_scope/add", ("fwd", "none")),
    ("", ("none", "none")),
])
def test_classify(op_name, want):
    assert scopes.classify(op_name) == want


def _feed(cfg, **more):
    return dict({"tokens": np.zeros((2, cfg.seq_len), np.int64)}, **more)


def _build(builder):
    """(loss, feed) of a builder's tiny program."""
    if builder == "bert":
        from paddle_tpu.models import bert
        cfg = bert.BertConfig.tiny()
        _, _, loss = bert.build_pretrain_program(cfg, use_input_mask=True)
        return loss, {
            "input_ids": np.zeros((2, cfg.seq_len), np.int64),
            "mlm_labels": np.zeros((2, cfg.seq_len, 1), np.int64),
            "input_mask": np.ones((2, cfg.seq_len), np.float32)}
    module = importlib.import_module("paddle_tpu.models." + builder)
    cfg = next(getattr(module, n) for n in dir(module)
               if n.endswith("Config")).tiny()
    _, loss, _ = module.build_causal_lm_program(cfg)
    if getattr(cfg, "position_streams", 0):
        return loss, _feed(cfg, positions=np.zeros((3, 2, cfg.seq_len),
                                                   np.int64))
    return loss, _feed(cfg)


_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?[\w.\-]+ = \S+ ([\w\-]+)\(.*?op_name="([^"]*)"',
    re.M)


def _step_op_names(builder, recompute):
    """The `op_name` of every instruction of the compiled tiny AMP step
    that a lowering made and the device runs: no argument, no constant
    (how many XLA keeps of a checkpoint's literals varies from compile to
    compile), no reducer's body (their `op_name` does not start at the
    jitted step)."""
    reset_programs(seed=0)
    loss, feed = _build(builder)
    fleet.init(is_collective=True)
    strategy = fleet.DistributedStrategy()
    strategy.amp = True
    if recompute:
        strategy.recompute = True
        strategy.recompute_configs = {"checkpoints": loss._layer_checkpoints}
    fleet.distributed_optimizer(paddle.optimizer.Adam(1e-3),
                                strategy).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    hlo = exe.compiled_hlo(feed, [loss])
    exe.close()
    return [op_name for opcode, op_name in _INSTRUCTION.findall(hlo)
            if opcode not in ("parameter", "constant")
            and op_name.startswith("jit(")]


# instructions whose scope is `none`, without and with recomputation: the
# update's scalar products of Adam's two beta powers
# (`_finalize_optimize_ops` sets no scope; each a multiply and the fusion
# around it) and, where the last segment leaves the loss to a pullback, the
# broadcast of the loss gradient's seed; keye's pullbacks also broadcast
# the zero cotangents of a segment's integer outputs (the selection)
UNSCOPED_RECOMPUTED = {"keye": 9}
BUILDERS = ("bert", "deepseek_v3", "keye", "lfm2", "ling", "mellum",
            "nemotron_h")


@pytest.mark.parametrize("recompute", [False, True],
                         ids=["plain", "recompute"])
@pytest.mark.parametrize("builder", BUILDERS)
def test_every_instruction_of_a_step_has_a_phase_and_known_scopes(
        builder, recompute):
    op_names = _step_op_names(builder, recompute)
    classes = [scopes.classify(n) for n in op_names]
    phases = {phase for phase, _ in classes}
    assert "none" not in phases, sorted(
        {n for n, (p, _) in zip(op_names, classes) if p == "none"})[:5]
    assert {"fwd", "bwd", "opt"} <= phases
    # recomputed work exactly where the program recomputes
    assert ("recompute" in phases) == recompute
    # every dotted name of a path is a phase or a catalogued scope
    dotted = {name for n in op_names for name in scopes._NAMES.findall(n)
              if re.fullmatch(r"[a-z_0-9]+(\.[a-z_0-9]+)+", name)}
    unknown = {d for d in dotted
               if d not in scopes.PHASES and not scopes.scope_of(d)}
    assert not unknown, unknown
    unscoped = [n for n, (_, s) in zip(op_names, classes) if s == "none"]
    want = UNSCOPED_RECOMPUTED.get(builder, 5) if recompute else 4
    assert len(unscoped) == want, sorted(set(unscoped))


def _scope_literals():
    """(file, line, name) of every string literal passed to `name_scope(`
    or `jax.named_scope(` under paddle_tpu/ (a conditional expression's
    two arms both count)."""
    out = []
    for folder, _, files in os.walk(os.path.join(ROOT, "paddle_tpu")):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            with open(path) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if not (isinstance(node, ast.Call) and node.args):
                    continue
                fn = node.func
                called = fn.attr if isinstance(fn, ast.Attribute) else \
                    getattr(fn, "id", None)
                if called not in ("name_scope", "named_scope"):
                    continue
                for lit in ast.walk(node.args[0]):
                    if isinstance(lit, ast.Constant) \
                            and isinstance(lit.value, str):
                        out.append((os.path.relpath(path, ROOT),
                                    node.lineno, lit.value))
    return out


def test_every_scope_the_package_sets_is_catalogued():
    literals = _scope_literals()
    assert len(literals) > 60
    missing = [(f, line, s) for f, line, s in literals
               if scopes.scope_of(s) is None]
    assert not missing, missing
    # and nothing is catalogued that nothing sets
    set_somewhere = {s for _, _, s in literals} | {"optimizer.*"}
    assert set(scopes.CATALOGUE) == set_somewhere, \
        set(scopes.CATALOGUE) ^ set_somewhere


def test_no_new_name_falls_under_an_existing_reader():
    """`benchmark/scopes.py` matches `s in op_name`: the names this round
    added hold none of the substrings the accepted readers look for."""
    read = ("moe.route", "moe.dispatch", "moe.experts", "moe.combine",
            "moe.latent_down", "moe.latent_up", "optimizer.adam",
            "attn.index.", "attn.attend.", "mla.attend", "ssm.in_proj",
            "ssm.conv", "ssm.scan", "ssm.gate_norm", "ssm.out_proj",
            "conv.in_proj", "conv.mix", "conv.out_proj", "kda.proj",
            "kda.conv", "kda.gate", "kda.scan", "kda.out", "ragged-dot",
            "flash_attention")
    added = list(scopes.PHASES) + [
        "embed.tokens", "head.norm", "head.untied", "head.loss", "head.mlm",
        "layer.residual", "ffn.dense", "attn.mask", "dsa.io", "moe.io",
        "moe.switch", scopes.REMAT_MARK]
    assert not [(a, r) for a in added for r in read if r in a or a in r]


def _documented_scopes():
    """The names in the first column of docs/observability.md's scope
    table."""
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        text = f.read()
    table = text.split("| scope | set by | read by |", 1)[1].split("\n\n")[0]
    names = set()
    for row in table.splitlines()[2:]:
        names.update(re.findall(r"`([^`]+)`", row.split("|")[1]))
    return names


def test_the_documented_scopes_are_the_catalogue():
    want = set(scopes.CATALOGUE) | set(scopes.PHASES) | {scopes.REMAT_MARK}
    assert _documented_scopes() == want, _documented_scopes() ^ want


def test_no_documented_scope_is_read_by_hand():
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        assert "by hand" not in f.read().split(
            "| scope | set by | read by |", 1)[1].split("\n\n")[0]


def test_the_catalogue_says_where_each_scope_is_set():
    for name, scope in scopes.CATALOGUE.items():
        literal = name.rstrip("*")
        for path in scope.set_by.split(", "):
            with open(os.path.join(ROOT, "paddle_tpu", path)) as f:
                assert f'"{literal}' in f.read(), (name, path)


@pytest.mark.parametrize("metric, layer", [
    ("head_time_pct", "Embedding and head"),
    ("attn_proj_time_pct", "Attention projections"),
    ("dense_ffn_time_pct", "Dense feed-forward"),
    ("residual_time_pct", "Residual stream")])
def test_a_layers_reader_adds_up_the_catalogues_scopes_of_that_layer(
        metric, layer):
    """The four new layers' shares: the reader's scopes, the metric's
    `layer` in BENCHMARK.json and the catalogue's say the same."""
    import json
    with open(os.path.join(ROOT, "benchmark", "metrics", metric + ".py")) as f:
        source = f.read()
    read = set(re.findall(r'"([a-z]+\.[a-z_.]+)"', source))
    assert read == {name for name, scope in scopes.CATALOGUE.items()
                    if scope.layer == layer}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [m for m in json.load(f)["per_layer"] if m["name"] == metric]
    assert entry["layer"] == layer
