"""Static donation/aliasing analysis.

The Executor donates written persistable state into the jitted step
(framework/executor.py _CompiledBlock): the update happens in place in HBM,
and the Scope's old buffer is DELETED the moment the dispatch starts. That
donation decision was historically observable only at run time — the copy
census (scripts/copy_audit.py) reads it out of compiled HLO, and the
staging/lazy-fetch machinery resolves conflicts dynamically. This module is
the static complement: from the program plus a (feed, fetch) signature it
predicts, before any compile, exactly which buffers the compiled block will
donate, and flags the aliasing hazards the runtime machinery exists to
absorb:

* fetch_of_donated — a fetch target that is written persistable state: a
  lazy FetchHandle would read deleted memory after the next dispatch, so
  the executor snapshots it with a device copy EVERY step (run()'s
  jnp.copy branch). Legal, but a per-step copy tax worth knowing about.
* write_after_donate — a donated buffer written more than once in the
  step: the in-place alias covers one live range, so XLA must insert a
  value-preserving copy whenever the intermediate value is still read
  (the alias-conflict class the FLAGS_min_donate_bytes floor was added
  for, docs/perf_notes.md "Copy census").
* feed_shadows_state — a feed name that is also referenced persistable
  state: the feed silently overrides the Scope value for the step and
  removes the buffer from the donated set (Executor._resolve_call
  collects _referenced_state_names without the feeds).

Both the prediction and the floor mirror the executor's own rules — the
multi-step (run_steps) path donates everything written; the per-step path
applies the FLAGS_min_donate_bytes floor.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from .findings import Finding

EMPTY = "@EMPTY@"


@dataclass
class DonationReport:
    state_names: List[str] = field(default_factory=list)
    written_state: List[str] = field(default_factory=list)
    donated: List[str] = field(default_factory=list)
    undonated_written: List[str] = field(default_factory=list)
    donated_bytes: int = 0
    floor: int = 0
    multi_k: int = 0
    findings: List[Finding] = field(default_factory=list)

    def to_dict(self) -> dict:
        return {
            "state_names": self.state_names,
            "written_state": self.written_state,
            "donated": self.donated,
            "undonated_written": self.undonated_written,
            "donated_bytes": self.donated_bytes,
            "floor": self.floor,
            "multi_k": self.multi_k,
            "findings": [f.to_dict() for f in self.findings],
        }


def _var_nbytes(var) -> int:
    n = 1
    for d in var.shape:
        n *= max(int(d), 1)
    try:
        item = np.dtype(var.dtype).itemsize
    except TypeError:
        item = 4
    return n * item


def analyze_donation(program, feed_names=(), fetch_names=(),
                     multi_k: int = 0,
                     min_donate_bytes: Optional[int] = None) \
        -> DonationReport:
    """Predict the compiled block's donation set for this signature and
    report aliasing hazards. Mirrors _CompiledBlock: state = referenced
    persistables minus feeds; donated = written state at or above the
    donation floor (everything written when multi_k, the k-step scan
    path)."""
    from ..flags import flag

    block = program.global_block()
    feed_names = set(feed_names)
    fetch_names = list(fetch_names)
    if min_donate_bytes is None:
        min_donate_bytes = 0 if multi_k else \
            int(flag("FLAGS_min_donate_bytes") or 0)

    referenced = set()
    for op in block.ops:
        referenced.update(op.input_names())
        referenced.update(op.output_names())
    referenced.discard(EMPTY)

    state, written, write_counts = [], [], {}
    written_set = set()
    for n in sorted(referenced):
        v = block.find_var_recursive(n)
        if v is not None and v.persistable and n not in feed_names:
            state.append(n)
    state_set = set(state)
    for i, op in enumerate(block.ops):
        for n in op.output_names():
            if n == EMPTY or n not in state_set:
                continue
            if n not in written_set:
                written.append(n)
                written_set.add(n)
            write_counts[n] = write_counts.get(n, 0) + 1

    donated, undonated = [], []
    donated_bytes = 0
    for n in written:
        v = block.find_var_recursive(n)
        nb = _var_nbytes(v) if v is not None else 0
        if min_donate_bytes <= 0 or nb >= min_donate_bytes:
            donated.append(n)
            donated_bytes += nb
        else:
            undonated.append(n)
    donated_set = set(donated)

    findings: List[Finding] = []
    for n in fetch_names:
        if n in donated_set:
            findings.append(Finding(
                check="fetch_of_donated", severity="warning",
                message=f"fetch target {n!r} is donated written state: a "
                        "lazy fetch must snapshot it (one device copy per "
                        "step — executor.run's written-persistable "
                        "snapshot branch)", var=n))
    for n in donated:
        if write_counts.get(n, 0) > 1:
            findings.append(Finding(
                check="write_after_donate", severity="warning",
                message=f"donated buffer {n!r} is written "
                        f"{write_counts[n]} times in one step: the "
                        "in-place alias covers one live range, so XLA "
                        "inserts a value-preserving copy for each "
                        "intermediate value still read", var=n))
    for n in sorted(feed_names):
        v = block.find_var_recursive(n)
        if v is not None and v.persistable:
            findings.append(Finding(
                check="feed_shadows_state", severity="warning",
                message=f"feed {n!r} is a persistable var: the feed "
                        "overrides its Scope value for this step and "
                        "removes it from the donated state set", var=n))

    return DonationReport(state_names=state, written_state=written,
                          donated=donated, undonated_written=undonated,
                          donated_bytes=donated_bytes,
                          floor=int(min_donate_bytes), multi_k=int(multi_k),
                          findings=findings)
