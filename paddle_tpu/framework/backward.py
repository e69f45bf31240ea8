"""append_backward: graph-level reverse-mode autodiff on the Program IR.

Reference counterpart: python/paddle/fluid/backward.py:1275 (+ C++ per-op grad
makers via core.get_grad_op_desc, backward.py:984). TPU-native difference: no
per-op hand-written grad kernels exist or are needed — each forward op's grad
is a single generic `__vjp__` op whose lowering calls jax.vjp on the forward
lowering (ops/registry.py). An op that declares a grad rule also gets its own
forward outputs as `FO:<slot>` inputs of that `__vjp__` op, the way the
reference's grad-op makers take Out beside X and Out@GRAD. Gradient
aggregation for multi-consumer vars uses the reference's rename+sum scheme
(backward.py _addup_repetitive_outputs_).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Set

from .program import (OpRole, Parameter, Variable, grad_var_name)
from .dtype import is_floating
from ..ops import registry


def _forward_closure(block, seed_names: Set[str], no_grad: Set[str]) -> Set[str]:
    """Vars computationally downstream of seeds (flow through ops)."""
    reach = set(seed_names)
    for op in block.ops:
        if registry.has(op.type) and _op_nondiff(op):
            continue
        ins = set(op.input_names())
        if ins & reach:
            for slot, names in op.outputs.items():
                opdef = registry.get(op.type) if registry.has(op.type) else None
                if opdef and slot in opdef.stateful_outputs:
                    continue
                for n in names:
                    if n not in no_grad:
                        reach.add(n)
    return reach


def _backward_closure(block, target: str) -> Set[str]:
    """Vars the target depends on."""
    need = {target}
    for op in reversed(block.ops):
        outs = set(op.output_names())
        if outs & need:
            need.update(op.input_names())
    return need


def _op_nondiff(op) -> bool:
    return op.attrs.get("op_role", 0) in (OpRole.Optimize,)


class _GradAccumulator:
    """Tracks grad contributions per var; emits sum ops when a var's grad has
    multiple producers (reference _addup_repetitive_outputs_)."""

    def __init__(self, block):
        self.block = block
        self.contribs: Dict[str, List[str]] = {}
        # grad names already produced by earlier append_backward calls must
        # not be reused — higher-order passes (grad-of-grad) get fresh names
        # (the reference's _rename_grad_ machinery)
        self._taken = set()
        # the program.name_scope a variable was produced under: the sum of
        # its repeated gradients is that group's device work
        self._scope_of: Dict[str, str] = {}
        for op in block.ops:
            self._taken.update(n for n in op.output_names()
                               if n != "@EMPTY@")
            if op.attrs.get("name_scope"):
                self._scope_of.update(dict.fromkeys(
                    op.output_names(), op.attrs["name_scope"]))

    def _base_name(self, var_name: str) -> str:
        gname = grad_var_name(var_name)
        k = 2
        while gname in self._taken:
            gname = f"{grad_var_name(var_name)}@{k}"
            k += 1
        return gname

    def add(self, var_name: str) -> str:
        lst = self.contribs.setdefault(var_name, [])
        gname = self._base_name(var_name)
        name = gname if not lst else f"{gname}@RENAME@{len(lst)}"
        lst.append(name)
        fwd = self.block.var(var_name)
        # grad vars stay differentiable-through: a later append_backward may
        # differentiate THROUGH them (grad-of-grad)
        self.block.create_var(name=name, shape=fwd.shape, dtype=fwd.dtype,
                              stop_gradient=False)
        return name

    def finalize(self, var_name: str) -> Optional[str]:
        lst = self.contribs.get(var_name)
        if not lst:
            return None
        if len(lst) == 1:
            return lst[0]
        gname = self._base_name(var_name)
        # sum all contributions into one var, then collapse the list
        sum_out = gname
        if lst[0] == gname:
            # first contribution already claimed the canonical name; sum into a
            # fresh var then treat it as canonical going forward
            sum_out = f"{gname}@MERGED"
        fwd = self.block.var(var_name)
        out_var = self.block.create_var(name=sum_out, shape=fwd.shape,
                                        dtype=fwd.dtype, stop_gradient=False)
        attrs = {"op_role": OpRole.Backward}
        if var_name in self._scope_of:
            attrs["name_scope"] = self._scope_of[var_name]
        self.block.append_op("sum", inputs={"X": list(lst)},
                             outputs={"Out": [sum_out]}, attrs=attrs)
        if all(getattr(self.block.var(n), "_is_selected_rows", False)
               for n in lst):   # sparse+sparse stays SelectedRows
            out_var._is_selected_rows = True
        self.contribs[var_name] = [sum_out]
        return sum_out


def append_backward(loss: Variable, parameter_list=None,
                    no_grad_set: Optional[Set[str]] = None,
                    callbacks=None):
    """Append backward ops computing d(loss)/d(param) for every trainable
    parameter. Returns [(param, grad_var)] like the reference."""
    block = loss.block
    program = block.program
    no_grad = set(no_grad_set or ())
    for v in block.vars.values():
        if v.stop_gradient and not isinstance(v, Parameter):
            no_grad.add(v.name)

    if parameter_list:
        params = [block.var(p) if isinstance(p, str) else p
                  for p in parameter_list]
    else:
        params = [p for p in program.all_parameters() if p.trainable]
    param_names = {p.name for p in params}

    relevant = (_forward_closure(block, param_names, no_grad)
                & _backward_closure(block, loss.name))
    relevant |= param_names

    acc = _GradAccumulator(block)

    # Seed: d(loss)/d(loss) = 1
    loss_grad = acc._base_name(loss.name)
    block.create_var(name=loss_grad, shape=loss.shape, dtype=loss.dtype,
                     stop_gradient=True)
    block.append_op("fill_constant",
                    inputs={},
                    outputs={"Out": [loss_grad]},
                    attrs={"shape": list(loss.shape) or [],
                           "dtype": "float32", "value": 1.0,
                           "op_role": OpRole.Backward | OpRole.Loss})
    acc.contribs[loss.name] = [loss_grad]

    # differentiate every non-optimizer op built so far — including the
    # __vjp__ ops of earlier append_backward calls, so grad-of-grad works
    # (the reference composes per-op DoubleGrad makers; ours composes
    # jax.vjp of the __vjp__ lowering itself)
    fwd_ops = [op for op in block.ops
               if op.attrs.get("op_role", 0) & OpRole.Optimize == 0
               and not (op.attrs.get("op_role", 0) & OpRole.Loss)]

    for op in reversed(fwd_ops):
        if not registry.has(op.type):
            continue
        opdef = registry.get(op.type)
        # outputs that might carry incoming grads
        out_slots = [s for s in op.outputs if s not in opdef.stateful_outputs]
        has_any_og = any(acc.contribs.get(n) for s in out_slots
                         for n in op.outputs[s])
        if not has_any_og:
            continue
        # differentiable input entries we actually need grads for
        diff_entries = []
        for slot, names in op.inputs.items():
            if slot in opdef.nondiff_slots:
                continue
            for i, n in enumerate(names):
                v = block.find_var_recursive(n)
                if v is None or not is_floating(v.dtype):
                    continue
                if n in no_grad:
                    continue
                if n in relevant:
                    diff_entries.append((slot, i))
        if not diff_entries:
            continue

        # Ops that overwrite their own input vars (While carried state,
        # in-place increments): by the time the __vjp__ op runs, the env
        # holds POST-op values under those names, which would corrupt the
        # re-lowered forward inside jax.vjp (a finished While's cond=False
        # re-runs zero iterations -> zero grads). Snapshot the pre-op
        # values with assign ops inserted right before the forward op and
        # point the vjp's regular inputs at the snapshots.
        out_names = {n for ns in op.outputs.values() for n in ns
                     if n != "@EMPTY@"}
        overlap = {n for ns in op.inputs.values() for n in ns
                   if n != "@EMPTY@" and n in out_names}
        snap = {}
        if overlap:
            pos = block.ops.index(op)
            for n in sorted(overlap):
                sname = f"{n}@PRE"
                while block.find_var_recursive(sname) is not None:
                    sname += "_"
                fv = block.var(n)
                block.create_var(name=sname, shape=fv.shape, dtype=fv.dtype,
                                 stop_gradient=True)
                block._insert_op(pos, "assign", inputs={"X": [n]},
                                 outputs={"Out": [sname]})
                snap[n] = sname
                pos += 1

        grad_inputs = {slot: [snap.get(n, n) for n in names]
                       for slot, names in op.inputs.items()}
        for slot in out_slots:
            og_names = []
            for n in op.outputs[slot]:
                g = acc.finalize(n)
                og_names.append(g if g is not None else "@EMPTY@")
            grad_inputs[f"OG:{slot}"] = og_names
        if opdef.grad is not None:
            # residuals for the op's grad rule: what the forward launch
            # already wrote
            for slot in opdef.residual_slots:
                if slot in op.outputs and "@EMPTY@" not in op.outputs[slot]:
                    grad_inputs[f"FO:{slot}"] = list(op.outputs[slot])

        grad_outputs = {}
        for slot, names in op.inputs.items():
            ig = []
            slot_has = False
            for i, n in enumerate(names):
                if (slot, i) in diff_entries:
                    ig.append(acc.add(n))
                    slot_has = True
                else:
                    ig.append("@EMPTY@")
            if slot_has:
                grad_outputs[f"IG:{slot}"] = ig

        # is_sparse embeddings get a SelectedRows grad op instead of the
        # dense __vjp__ (reference lookup_table_op.cc is_sparse grad branch)
        if op.type in ("lookup_table", "lookup_table_v2") \
                and op.attrs.get("is_sparse", False) \
                and list(grad_outputs) == ["IG:W"]:
            block.append_op(
                "lookup_table_sparse_grad", inputs=grad_inputs,
                outputs=grad_outputs,
                attrs={"padding_idx": op.attrs.get("padding_idx", -1),
                       "op_role": OpRole.Backward})
            gvar = block.var(grad_outputs["IG:W"][0])
            gvar._is_selected_rows = True
            continue

        attrs = registry.make_vjp_attrs(op, diff_entries, out_slots)
        block.append_op("__vjp__", inputs=grad_inputs, outputs=grad_outputs,
                        attrs=attrs)

    # finalize param grads
    params_and_grads = []
    for p in params:
        g = acc.finalize(p.name)
        if g is None:
            continue
        params_and_grads.append((p, block.var(g)))
    return params_and_grads


def gradients(targets, inputs, target_gradients=None, no_grad_set=None):
    """fluid.gradients parity: d(targets)/d(inputs)."""
    if not isinstance(targets, (list, tuple)):
        targets = [targets]
    if not isinstance(inputs, (list, tuple)):
        inputs = [inputs]
    assert len(targets) == 1, "v1 supports a single target"
    block = targets[0].block
    for x in inputs:
        v = block.var(x.name if isinstance(x, Variable) else x)
        v.stop_gradient = False  # grads explicitly requested for these
    pgs = append_backward(targets[0],
                          parameter_list=list(inputs),
                          no_grad_set=no_grad_set)
    by_name = {p.name: g for p, g in pgs}
    return [by_name.get(x.name if isinstance(x, Variable) else x)
            for x in inputs]
