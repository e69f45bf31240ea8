"""The step a Program lowers to is a function of the Program alone.

XLA's persistent compile cache keys a step by its HLO, so a pass that
orders an op's outputs (or anything else that reaches the trace) by
iterating a `set` of variable names gives every process another step: the
entry is built and written every time and no later process asks for it
(the hybrid cell's 165 MB step, PERF.md section 6, PR 34 and PR 35).
Python randomises string hashes per process and a test cannot change its
own process's hash seed, so each case here traces one route's train step
in two fresh subprocesses, under `PYTHONHASHSEED` 1 and 2, and compares
the digests of `exe.step_jaxpr(...)`'s text. Run as a script
(`python tests/test_program_determinism.py <route>`) this file is that
child: it prints one JSON line.
"""
import hashlib
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HASH_SEEDS = (1, 2)
TIMEOUT_S = 240


# ---------------------------------------------------------------------------
# the routes: each returns (executor, feed, fetch_list, k)
# ---------------------------------------------------------------------------

def _fleet_minimize(loss, optimizer, **strategy):
    from paddle_tpu.distributed import fleet
    fleet.init(is_collective=True)
    s = fleet.DistributedStrategy()
    for key, value in strategy.items():
        setattr(s, key, value(loss) if callable(value) else value)
    fleet.distributed_optimizer(optimizer, s).minimize(loss)


def _layer_checkpoints(loss):
    return {"checkpoints": list(loss._layer_checkpoints)}


def _bert_step(**strategy):
    import paddle_tpu as paddle
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import bert
    cfg = bert.BertConfig(vocab_size=256, hidden_size=32, num_layers=4,
                          num_heads=2, intermediate_size=64,
                          max_position=32, seq_len=16,
                          hidden_dropout=0.1, attention_dropout=0.1)
    _, _, loss = bert.build_pretrain_program(cfg)
    opt = paddle.optimizer.Adam(learning_rate=1e-3)
    if strategy:
        _fleet_minimize(loss, opt, **strategy)
    else:
        opt.minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(1)
    feed = {"input_ids": rng.randint(0, 256, (8, 16)).astype(np.int64),
            "mlm_labels": rng.randint(0, 256, (8, 16, 1)).astype(np.int64)}
    return exe, feed, [loss], None


def _hybrid_step():
    """The tiny hybrid preset's AMP step with a checkpoint at every layer
    boundary, two steps a call: the benchmark cell's route
    (`tests/test_nemotron_h.py` `_amp_step(True)`)."""
    import paddle_tpu as paddle
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import nemotron_h
    cfg = nemotron_h.NemotronHConfig.tiny()
    cfg.seq_len, cfg.chunk_size = 128, 32
    _, loss, _ = nemotron_h.build_causal_lm_program(cfg)
    _fleet_minimize(loss, paddle.optimizer.Adam(1e-3), amp=True,
                    recompute=True, recompute_configs=_layer_checkpoints)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    ids = np.random.RandomState(0).randint(0, 256, (2, 1, 128)).astype(
        np.int64)
    return exe, {"tokens": ids}, [loss], 2


def _linear_attention_step():
    """The tiny linear-attention preset's AMP step with a checkpoint at
    every layer boundary, two steps a call: the benchmark cell's route
    (`tests/test_ling.py` `_amp_step(True)`)."""
    import paddle_tpu as paddle
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import ling
    cfg = ling.LingConfig.tiny()
    cfg.seq_len, cfg.kda_chunk_size = 128, 64
    _, loss, _ = ling.build_causal_lm_program(cfg)
    _fleet_minimize(loss, paddle.optimizer.Adam(1e-3), amp=True,
                    recompute=True, recompute_configs=_layer_checkpoints)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    ids = np.random.RandomState(0).randint(0, 256, (2, 1, 128)).astype(
        np.int64)
    return exe, {"tokens": ids}, [loss], 2


ROUTES = {
    # route: (builder, virtual CPU devices, the op types its passes leave in
    # the program: a route that fell back to the plain program would pass
    # for the wrong reason)
    "plain": (_bert_step, 1, ()),
    "recompute_hybrid_amp": (_hybrid_step, 1, ("__segment__",)),
    "recompute_linear_attention_amp": (_linear_attention_step, 1,
                                       ("__segment__",)),
    "recompute_bert": (lambda: _bert_step(
        recompute=True, recompute_configs=_layer_checkpoints), 1,
        ("__segment__",)),
    "layer_scan": (lambda: _bert_step(layer_scan=True), 1,
                   ("__layer_scan__",)),
    "layer_scan_recompute": (lambda: _bert_step(
        layer_scan=True, recompute=True,
        recompute_configs=_layer_checkpoints), 1,
        ("__layer_scan__", "__segment__")),
    "zero1_dp4": (lambda: _bert_step(sharding=True), 4,
                  ("__zero_update__",)),
    "zero3_layer_scan_dp4": (lambda: _bert_step(
        layer_scan=True, sharding=True, sharding_configs={"stage": 3}), 4,
        ("__layer_scan__", "__zero_gather__", "__zero_update__")),
    "gradient_merge": (lambda: _bert_step(
        gradient_merge=True, gradient_merge_configs={"k_steps": 2}), 1,
        ("where",)),
}


def jaxpr_text(route: str):
    """(text, missing op types): the route's step as jaxpr text with what
    may differ between two processes of ONE program cut (source lines,
    object addresses, and the printing order of a `frozenset`: a
    mesh-attached step prints `manual_axes=frozenset({...})`, a jaxpr
    parameter's value, whose order reaches no HLO), and which of the op
    types its passes should leave the program does not hold."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.parallel.zero import _iter_op_types
    from paddle_tpu.testing import reset_programs
    reset_programs(0)
    build, _, pass_ops = ROUTES[route]
    exe, feed, fetch, k = build()
    held = set(_iter_op_types(fluid.default_main_program()))
    text = str(exe.step_jaxpr(feed, fetch, k=k))
    text = re.sub(r"[\w/.\-]+\.py:\d+", "", text)
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    text = re.sub(
        r"frozenset\(\{([^}]*)\}\)",
        lambda m: "frozenset({%s})" % ", ".join(
            sorted(p.strip() for p in m.group(1).split(","))), text)
    return text, [t for t in pass_ops if t not in held]


def _child(route: str) -> None:
    text, missing = jaxpr_text(route)
    print(json.dumps({"route": route, "chars": len(text),
                      "missing_ops": missing,
                      "hash_seed": os.environ.get("PYTHONHASHSEED"),
                      "sha256": hashlib.sha256(text.encode()).hexdigest()}))


# ---------------------------------------------------------------------------
# the test
# ---------------------------------------------------------------------------

def digests(route: str):
    """One fresh process a hash seed, started together; each one's JSON."""
    from paddle_tpu.testing import cpu_mesh_env
    procs = []
    for hs in HASH_SEEDS:
        env = cpu_mesh_env(ROUTES[route][1])
        env["PYTHONHASHSEED"] = str(hs)
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), route], env=env,
            cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True))
    out = []
    try:
        for p in procs:
            stdout, stderr = p.communicate(timeout=TIMEOUT_S)
            assert p.returncode == 0, f"{route}: child failed:\n{stderr}"
            out.append(json.loads(stdout.strip().splitlines()[-1]))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    return out


@pytest.mark.parametrize("route", list(ROUTES))
def test_two_hash_seeds_give_one_program(route):
    got = digests(route)
    assert [g["hash_seed"] for g in got] == [str(s) for s in HASH_SEEDS]
    assert got[0]["chars"] > 1000 and not got[0]["missing_ops"], got
    assert len({g["sha256"] for g in got}) == 1, (
        f"{route}: the step's jaxpr follows the hash seed: some pass "
        f"iterates a set of names on the way to the program: {got}")


if __name__ == "__main__":
    _child(sys.argv[1])
