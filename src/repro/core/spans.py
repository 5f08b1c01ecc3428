"""Names of the program's profiler scopes and spans, in one place.

Device scopes are ``jax.named_scope`` names: they land in the compiled
HLO's ``op_name`` metadata (the ``tf_op`` of each operation in a
profiler trace), so device time can be read per layer.  Where scopes
nest, the innermost one names the operation.  Host spans are
``jax.profiler.TraceAnnotation`` names on the path ``run`` ->
``_run_fused``; with no profiler session open they cost well under a
microsecond each.  The benchmark's trace reduction imports these names.
"""
from __future__ import annotations

# -- device scopes ----------------------------------------------------------
#: the dynamic push/pull choice from the frontier's statistics
DIRECTION = "direction"
#: the push branch's sparse frontier: compaction, edge gather and fit test
FRONTIER = "frontier"
#: forming messages from endpoint state: edge slices, index clamps,
#: ``spred``/``tpred``/``vprop`` and the masking of failed edges
EDGE_GATHER = "edge_gather"
#: the reduction of messages into vertices (XLA segment ops or Pallas)
EDGE_REDUCE = "edge_reduce"
#: the consistency schedule's combine of chunk partials (DRF1, DRFrlx)
SCHEDULE = "schedule"
#: the rest of a fused-loop iteration: vertex update, convergence test
VERTEX_STEP = "vertex_step"

SCOPES = (DIRECTION, FRONTIER, EDGE_GATHER, EDGE_REDUCE, SCHEDULE,
          VERTEX_STEP)

# -- host spans -------------------------------------------------------------
#: all of one ``run`` call
RUN = "repro.run"
#: ``EdgeContext.create``: a plan-cache lookup, or the context's build
CONTEXT = "repro.context"
#: ``program.init`` and the copy of the initial state to the device
INIT = "repro.init"
#: tracing the runner to a jaxpr (``make_jaxpr`` in ``_jit_hoisted``)
TRACE = "repro.trace"
#: lowering and compiling the runner, a persistent-cache load included
COMPILE = "repro.compile"
#: the call of the compiled fused runner (enqueue)
DISPATCH = "repro.dispatch"
#: waiting for the fused runner's results (``block_until_ready``)
WAIT = "repro.wait"
#: reading the iteration count and the trace buffers back to the host
DECODE = "repro.decode"

SPANS = (RUN, CONTEXT, INIT, TRACE, COMPILE, DISPATCH, WAIT, DECODE)
