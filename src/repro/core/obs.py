"""Names of the profiler spans and scopes on the solve, PCG and multigrid
paths, and a process-wide counter for work that has no object to keep
counts on.

Spans are ``jax.profiler.TraceAnnotation(name)``: host intervals written to
the profiler's trace, on the device ops' clock, only while a profiler runs.
Scopes are ``jax.named_scope(name)`` inside jitted executors: they name the
HLO ops traced within (``op_name`` metadata) and change nothing else.
Per-solver counts live in ``SpTRSV.stats()``; this counter is for ``pcg``,
for the multigrid V-cycle, which runs many solvers, and for the SpMV
operators they build.
"""
from __future__ import annotations

import collections

# host spans
SOLVE = "sptrsv.solve"          # the whole of SpTRSV.solve
PCG_SETUP = "pcg.setup"         # pcg before its loop
PCG_ITER = "pcg.iter"           # one pcg loop iteration
PCG_READBACK = "pcg.readback"   # one host read of a device value in pcg
MG_VCYCLE = "mg.vcycle"         # one multigrid preconditioner apply

# device scopes
PERMUTE = "sptrsv.permute"      # b[perm] in, x[pos] out
SEGMENT = "sptrsv.segment"      # one segment of the level-set executor
MG_SMOOTH = "mg.smooth"         # SYMGS's SpMV and vector updates
MG_TRANSFER = "mg.transfer"     # restriction residual, injection, prolongation

# counters
READBACKS = "pcg.readbacks"     # one per PCG_READBACK span
ITERATIONS = "pcg.iterations"   # pcg loop iterations entered
MATVEC_TRACES = "pcg.matvec_traces"  # traces of pcg's shared SpMV
MG_VCYCLES = "mg.vcycles"       # one per MG_VCYCLE span
MG_DISPATCHES = "mg.dispatches"  # jitted programs the V-cycles enqueued
SPMV_DIA_ENTRIES = "spmv.dia_entries"  # entries of SpMV operators built as DIA
SPMV_ELL_ENTRIES = "spmv.ell_entries"  # entries of those built as ELL

_counts: collections.Counter = collections.Counter()


def count(name: str, k: int = 1) -> None:
    _counts[name] += k


def snapshot() -> dict:
    """Every count since the process started."""
    return dict(_counts)
