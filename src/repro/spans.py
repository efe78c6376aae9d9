"""Names of the program's profiler spans, and the one way to open them.

Every span is a ``jax.profiler.TraceAnnotation``: inactive (under a
microsecond) unless a profiler trace is running, and then an event on the
host plane of the same ``.xplane.pb`` as the device's ops, on the
profiler's clock.  The program, the benchmark's readers and ``PERF.md``
use the names below.

Ingest (``LayoutEngine.ingest`` and the fused steps of the jax and
pallas backends):

* ``engine.ingest`` — one ``ingest`` call; its self time is the wait on
  the caller's batch iterator.
* ``engine.stage`` — per batch: pad to the bucket, the valid mask, the
  host→device copies and the enqueue of the compiled step.
* ``engine.device_wait`` — per batch: the pulls of the step's outputs,
  where the host blocks on the device.
* ``engine.fold`` — per batch: the outputs turned into a tightening
  partial and merged, plus the spill-buffer and observation work when
  they are set.
* ``engine.publish`` — per call: the tightened descriptions published.

Serve (``QueryServer``):

* ``serve.coalesce`` — the dispatcher thread waiting for a batch.
* ``serve.dispatch`` — one dispatch (argument ``n``: tickets).
* ``serve.probe`` — per attempt: replica capture, cache activation,
  signatures, cache lookup and miss dedupe.
* ``serve.route`` — per attempt with misses: tensorize, the engine's
  routing dispatch and the torn-epoch check (argument ``misses``).
* ``serve.finish`` — per dispatch: cache inserts, tracker record and
  ticket completion.

Device step: ``jax.named_scope`` names over the phases of the jax
backend's fused ingest step (``_ingest_jax_padded``), carried into the
``op_name`` metadata of the compiled step's device ops; they change no
op:

* ``step.predicates`` — the range, IN and advanced predicate matrix;
* ``step.in_lookup`` — inside it, the IN cuts' lookup (the one-hot
  product with the IN masks);
* ``step.locate`` — each row's block (path-matrix product or descent);
* ``step.minmax`` — the per-block minimum and maximum scatters;
* ``step.cat_stats`` — the categorical one-hot and the one-hot stats
  product (counts, categorical bits, advanced flags).

Build (``core.greedy.build_greedy``):

* ``build.score`` — per node the greedy builder tries to split: the
  legal cuts' children described and scored against the workload.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation as span

ENGINE_INGEST = "engine.ingest"
ENGINE_STAGE = "engine.stage"
ENGINE_DEVICE_WAIT = "engine.device_wait"
ENGINE_FOLD = "engine.fold"
ENGINE_PUBLISH = "engine.publish"

SERVE_COALESCE = "serve.coalesce"
SERVE_DISPATCH = "serve.dispatch"
SERVE_PROBE = "serve.probe"
SERVE_ROUTE = "serve.route"
SERVE_FINISH = "serve.finish"

STEP_PREDICATES = "step.predicates"
STEP_IN_LOOKUP = "step.in_lookup"
STEP_LOCATE = "step.locate"
STEP_MINMAX = "step.minmax"
STEP_CAT_STATS = "step.cat_stats"

BUILD_SCORE = "build.score"

ENGINE = (ENGINE_INGEST, ENGINE_STAGE, ENGINE_DEVICE_WAIT, ENGINE_FOLD,
          ENGINE_PUBLISH)
SERVE = (SERVE_COALESCE, SERVE_DISPATCH, SERVE_PROBE, SERVE_ROUTE,
         SERVE_FINISH)
STEP = (STEP_PREDICATES, STEP_IN_LOOKUP, STEP_LOCATE, STEP_MINMAX,
        STEP_CAT_STATS)
