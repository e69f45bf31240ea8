"""Save/load: persistables, whole programs, inference models.

Reference counterpart: python/paddle/fluid/io.py (save/load_persistables :598,
:966; save/load_inference_model :1164,:1669) backed by C++ save_op/load_op.
TPU-native: tensors serialize via numpy .npz (threaded orbax checkpointing is
used by the higher-level paddle.distributed path); programs serialize as JSON
descs (framework/program.py to_desc/from_desc).

Crash safety (docs/resilience.md): every tensor payload is written to a
sibling temp file and atomically os.replace()d into place — a save that
dies mid-write (the 'ckpt.write' fault site fires right before publish)
leaves the previous file intact, never a torn one. save_persistables also
emits a checksum manifest that load_persistables verifies, so silent
corruption surfaces as a typed error instead of garbage weights; versioned
keep-N checkpoints with fallback live in resilience.CheckpointManager.
"""
from __future__ import annotations

import json
import os
from typing import Optional

import numpy as np

from .framework.program import Program, default_main_program
from .framework.scope import global_scope
from .resilience.faults import fault_point

__all__ = ["save_persistables", "load_persistables", "save_params",
           "load_params", "save_inference_model", "load_inference_model",
           "save", "load"]


def _persistable_names(program: Program, scope):
    names = []
    for v in program.list_vars():
        if v.persistable and scope.has(v.name):
            names.append(v.name)
    return names


def _portable_arrays(program: Program, scope) -> dict:
    """Checkpoint payload for `program`: persistable scope values, with
    ZeRO-1 flat optimizer-state buckets split back into their per-param
    views (parallel/zero.py) — checkpoints are ALWAYS the unsharded format,
    so a replicated program loads them directly and a ZeRO program adopts
    them back into flat shards (zero.adopt_unsharded_state, from
    Executor._resolve_call), in either direction."""
    arrays = {n: np.asarray(scope.find(n))
              for n in _persistable_names(program, scope)}
    from .parallel.zero import unbucket_state_for_save
    return unbucket_state_for_save(program, arrays)


def _atomic_savez(path: str, arrays: dict):
    """Write an npz to `path` via temp file + fsync + atomic rename. The
    'ckpt.write' fault fires before the rename: an injected (or real) crash
    there leaves only the .tmp file, so the previous checkpoint survives."""
    tmp = path + f".tmp.{os.getpid()}"
    with open(tmp, "wb") as f:     # open fh: np.savez must not append .npz
        np.savez(f, **arrays)
        f.flush()
        os.fsync(f.fileno())
    fault_point("ckpt.write")
    os.replace(tmp, path)


def _manifest_path(path: str) -> str:
    return path + ".manifest.json"


def save_persistables(executor=None, dirname=None, main_program=None,
                      filename=None):
    program = main_program or default_main_program()
    scope = global_scope()
    os.makedirs(dirname, exist_ok=True)
    arrays = _portable_arrays(program, scope)
    path = os.path.join(dirname, filename or "persistables.npz")
    _atomic_savez(path, arrays)
    from .resilience.checkpoint import write_manifest
    write_manifest(dirname, -1, [os.path.basename(path)],
                   manifest_name=os.path.basename(_manifest_path(path)))
    return path


def load_persistables(executor=None, dirname=None, main_program=None,
                      filename=None):
    path = os.path.join(dirname, filename or "persistables.npz")
    mpath = _manifest_path(path)
    if os.path.exists(mpath):     # legacy checkpoints carry no manifest
        from .framework.errors import PreconditionNotMet
        from .resilience.checkpoint import validate_manifest
        if validate_manifest(dirname,
                             manifest_name=os.path.basename(mpath)) is None:
            raise PreconditionNotMet(
                "checkpoint %s fails its manifest checksum — corrupted or "
                "torn data/manifest, or a save crashed between publishing "
                "the data file and its manifest (two flat files cannot "
                "publish atomically together; for real crash-tolerance use "
                "resilience.CheckpointManager, whose directory checkpoints "
                "publish in one rename and fall back automatically)", path)
    scope = global_scope()
    with np.load(path) as data:
        for n in data.files:
            scope.set(n, data[n])


save_params = save_persistables
load_params = load_persistables


def save(program: Optional[Program] = None, model_path: str = "model"):
    """Whole-model save: program desc JSON + persistables npz
    (reference io.py:1669 save). Each file publishes atomically; a crash
    between the two renames can still pair a new desc with old params —
    use resilience.CheckpointManager when that window matters."""
    program = program or default_main_program()
    os.makedirs(os.path.dirname(model_path) or ".", exist_ok=True)
    dtmp = model_path + f".pdmodel.tmp.{os.getpid()}"
    with open(dtmp, "w") as f:
        json.dump(program.to_desc(), f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(dtmp, model_path + ".pdmodel")
    scope = global_scope()
    _atomic_savez(model_path + ".pdparams", _portable_arrays(program, scope))


def load(program: Optional[Program] = None, model_path: str = "model"):
    scope = global_scope()
    with np.load(model_path + ".pdparams" if not model_path.endswith(".npz")
                 else model_path) as data:
        for n in data.files:
            scope.set(n, data[n])


def save_inference_model(dirname, feeded_var_names, target_vars, executor=None,
                         main_program=None, model_filename=None,
                         params_filename=None):
    """Prune program to the inference slice feed->fetch and save
    (reference io.py:1164)."""
    program = main_program or default_main_program()
    inference_program = program.clone(for_test=True)
    _prune_to_targets(inference_program,
                      [v.name if hasattr(v, "name") else v
                       for v in target_vars])
    os.makedirs(dirname, exist_ok=True)
    meta = {"feed": list(feeded_var_names),
            "fetch": [v.name if hasattr(v, "name") else v
                      for v in target_vars]}
    with open(os.path.join(dirname, model_filename or "__model__"), "w") as f:
        json.dump({"program": inference_program.to_desc(), "meta": meta}, f)
    scope = global_scope()
    arrays = {n: np.asarray(scope.find(n))
              for n in _persistable_names(inference_program, scope)}
    np.savez(os.path.join(dirname, params_filename or "params.npz"), **arrays)
    return meta["fetch"]


def _prune_to_targets(program: Program, target_names):
    """Dead-op elimination backwards from targets (reference Program._prune)."""
    block = program.global_block()
    needed = set(target_names)
    kept = []
    for op in reversed(block.ops):
        if set(op.output_names()) & needed:
            kept.append(op)
            needed.update(op.input_names())
    block.ops = list(reversed(kept))
    program.bump_version()


def load_inference_model(dirname, executor=None, model_filename=None,
                         params_filename=None):
    with open(os.path.join(dirname, model_filename or "__model__")) as f:
        payload = json.load(f)
    program = Program.from_desc(payload["program"])
    scope = global_scope()
    with np.load(os.path.join(dirname, params_filename or "params.npz")) as d:
        for n in d.files:
            scope.set(n, d[n])
    meta = payload["meta"]
    fetch_vars = [program.global_block().var(n) for n in meta["fetch"]]
    return program, meta["feed"], fetch_vars


# data loading surface (paddle.io.* in 2.0; fluid.io.DataLoader in 1.x) —
# reference reader.py / fluid/dataloader/
from .dataloader import (DataLoader, Dataset, IterableDataset,  # noqa: E402
                         TensorDataset, Subset, random_split, Sampler,
                         SequenceSampler, RandomSampler, BatchSampler,
                         DistributedBatchSampler, DataFeeder)
