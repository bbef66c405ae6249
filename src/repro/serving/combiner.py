"""Device-resident partial ensemble combine (DESIGN.md §4).

Every device that hosts a worker has one combiner.  Its workers fold their
weighted predictions into a shared per-(request, segment) partial *on the
device*, and the combiner posts **one** ``Message(s, None, partial, rid,
count)`` per device per segment: one device->host copy (the ``copy``
stage) and one accumulator message, however many members share the
device.

How the flush trigger stays deterministic under coalescing: the broadcaster
assigns every (segment, model) pair to a *specific* worker instance
(round-robin striping across data-parallel instances, system.py), so at
``begin()`` time the system knows exactly how many member contributions each
device will produce for each segment.  The coalescing batcher may split one
member's segment across several batches, so contributions arrive as
row-ranges — the combiner therefore counts **rows, not messages**: a segment
flushes the moment ``members_on_device × segment_rows`` rows have been
folded, which is reached exactly once however the spans were packed.

Early-forward audit (chunk-granular pipeline, DESIGN.md §3): senders now
commit a (request, segment) the moment its last chunk materializes —
before the slot retires, and under priority reordering possibly *out of
segment order* and interleaved arbitrarily across members.  The row
arithmetic above is already order-free (each (segment, member) contributes
its rows exactly once, whenever it arrives), so nothing here changes; the
same holds for the `unexpect`/`expect_one` steal migration, which operates
on counts, not arrival order.

Fold at dispatch, commit at the sender (DESIGN.md §4): the chip runs
programs in the order they were dispatched, so a fold dispatched after the
sender's device wait queues behind every step dispatched meanwhile — the
other member's and later requests'.  The worker's *predictor* therefore
calls :meth:`add` for a (request, segment) right behind the step that
carries its last span: the fold's inputs are futures, nothing blocks, and
the fold program enters the device queue directly behind its own step.
The *sender* calls :meth:`commit` once the step's output is materialized,
has passed the NaN guard and the sender has won the in-flight ledger entry
(the pop-gate, DESIGN.md §10): only committed rows count toward the flush.
A folded contribution that is never committed (its worker quarantined and
the unit replayed or forgiven, its member demoted after dispatch, its
chunk poisoned) must leave no trace: the partial keeps a reference to each
contribution folded into it, and a partial posted with any uncommitted
fold is rebuilt from its committed contributions alone (``fold_rebuilds``;
the slow path).

Combination rules are applied member-side, so the partial is always additive:
  mean/weighted  partial[lo:hi] += w_m · P_m[lo:hi]
  vote           partial[lo:hi] += w_vote · onehot(argmax P_m[lo:hi])
  pallas         partial[lo:hi]  = ensemble_combine(P_m[None], [w_m],
                 partial[lo:hi]) — the accumulate-into-partial Pallas kernel
                 variant, applied to the span's rows
and the accumulator's per-message work collapses to ``Y[lo:hi] += partial``.
"""
from __future__ import annotations

import queue
import threading
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from repro.serving.metrics import StageTimers
from repro.serving.segments import Message, Request


class _Fold:
    """One member contribution folded into a partial at dispatch time; it
    counts toward the flush only once committed."""
    __slots__ = ("part", "contrib", "w", "row_lo", "rows", "committed")

    def __init__(self, part, contrib, w: float, row_lo: int, rows: int):
        self.part = part           # the _SegPartial it was folded into
        self.contrib = contrib     # kept for a rebuild until the post
        self.w = w
        self.row_lo = row_lo
        self.rows = rows
        self.committed = False


class _SegPartial:
    __slots__ = ("acc", "rows", "folds")

    def __init__(self):
        self.acc = None        # np.ndarray or jax.Array (device-resident)
        self.rows = 0          # committed member-rows
        self.folds = []        # every _Fold in acc, committed or not


class DeviceCombiner:
    """One per device hosting >= 1 worker.  ``add()`` is called from worker
    predictor threads and ``commit()`` from their sender threads; a
    per-combiner lock serializes the fold bookkeeping (the device math
    itself is dispatched asynchronously)."""

    def __init__(self, name: str, prediction_queue: "queue.Queue[Message]",
                 timers: Optional[StageTimers] = None, tracer=None,
                 device=None):
        self.name = name
        self.device = device   # the jax.Device its workers run on, if any
        self.prediction_queue = prediction_queue
        self.timers = timers if timers is not None else StageTimers()
        self.tracer = tracer
        self._tr_track = f"combine.{name}"
        # ring cached once: rings are cleared in place, never replaced
        self._tr_ring = tracer.ring(self._tr_track) \
            if tracer is not None else None
        self._lock = threading.Lock()
        # rid -> {s: (member contributions, expected member-rows)}
        self._expected: Dict[int, Dict[int, Tuple[int, int]]] = {}
        self._parts: Dict[Tuple[int, int], _SegPartial] = {}
        self.partials_posted = 0

    # ---- request lifecycle ---------------------------------------------------
    def begin(self, req: Request, expected: Dict[int, int]) -> None:
        """Register how many member contributions each segment of ``req``
        will see on this device.  The flush trigger is row-based: segment
        ``s`` completes after ``expected[s] * (end(s)-start(s))`` rows."""
        with self._lock:
            self._expected[req.rid] = {
                s: (n, n * (req.bounds(s)[1] - req.bounds(s)[0]))
                for s, n in expected.items() if n}

    def finish(self, rid: int) -> None:
        """Drop any state for a completed/failed request (idempotent)."""
        with self._lock:
            self._expected.pop(rid, None)
            for key in [k for k in self._parts if k[0] == rid]:
                del self._parts[key]

    # ---- expected-map migration (work stealing, DESIGN.md §8) ----------------
    def unexpect(self, req: Request, s: int) -> bool:
        """Remove ONE expected member contribution for (``req``, ``s``) — the
        inverse of one unit of :meth:`begin` — because its queued descriptor
        was re-routed to a data-parallel sibling on another device.  Returns
        False when the request is no longer tracked here (completed or torn
        down), in which case the caller must not register the expectation
        elsewhere.  If other members' rows already closed the reduced row
        count, the partial flushes immediately — exactly the message the
        accumulator would have seen had the stolen member never been striped
        to this device."""
        flush = None
        with self._lock:
            expected = self._expected.get(req.rid)
            if expected is None or s not in expected:
                return False
            count, want_rows = expected[s]
            lo, hi = req.bounds(s)
            count -= 1
            want_rows -= hi - lo
            part = self._parts.get((req.rid, s))
            if count <= 0:
                # no member left on this device: nothing here can commit
                # (each (segment, member) routes to exactly one instance),
                # so whatever was folded ahead is dropped with the partial
                self._parts.pop((req.rid, s), None)
                del expected[s]
            elif part is not None and part.rows >= want_rows:
                flush = (part, count)
                del self._parts[(req.rid, s)]
                del expected[s]
            else:
                expected[s] = (count, want_rows)
            if not expected:
                del self._expected[req.rid]
        if flush is not None:
            self._post(req, s, *flush)
        return True

    def expect_one(self, req: Request, s: int) -> None:
        """Register one additional expected member contribution for
        (``req``, ``s``) — the destination side of a stolen descriptor."""
        lo, hi = req.bounds(s)
        with self._lock:
            expected = self._expected.setdefault(req.rid, {})
            count, want_rows = expected.get(s, (0, 0))
            expected[s] = (count + 1, want_rows + (hi - lo))

    # ---- the fold (predictor) and the commit (sender) ------------------------
    def add(self, req: Request, s: int, m: int, P,
            row_lo: int = 0) -> Optional[_Fold]:
        """Fold member ``m``'s rows ``[row_lo, row_lo+len(P))`` of segment
        ``s`` into the device partial and return the fold, which counts
        toward the flush only once :meth:`commit` is called with it.  ``P``
        may be a numpy array (fake workers) or a device array — device
        arrays stay resident until the single flush transfer, and nothing
        here waits for them.  The ``combine`` stage times the fold.
        Returns None when the request is no longer tracked here."""
        # quantized members forward (q, per-row scale) tuples
        nrows = int(P[0].shape[0]) if isinstance(P, tuple) else int(P.shape[0])
        with self.timers.stage("combine") as fold:
            # the heavy elementwise math runs outside the lock; only the
            # accumulate + bookkeeping is serialized
            w = req.weights[m]
            contrib = self._contribution(req, P, w)
            with self._lock:
                expected = self._expected.get(req.rid)
                if expected is None or s not in expected:   # torn down
                    return None
                part = self._parts.setdefault((req.rid, s), _SegPartial())
                part.acc = self._fold(req, part.acc, contrib, w, s, row_lo)
                f = _Fold(part, contrib, w, row_lo, nrows)
                part.folds.append(f)
        self.timers.inc("folds_ahead")
        tr = self.tracer
        if tr is not None and tr.enabled:
            self._tr_ring.append(
                ("X", "combine", fold.t0, fold.t1 - fold.t0, req.rid,
                 s, m, None))
        return f

    def commit(self, req: Request, s: int, f: Optional[_Fold]) -> None:
        """Count fold ``f`` (from :meth:`add`) toward its segment's flush
        and post the partial once the expected row count is reached.  The
        caller commits a fold at most once, and only after the sender won
        the unit's ledger entry; a fold whose partial was torn down or
        dropped meanwhile is ignored."""
        if f is None:
            return
        flush = None
        with self._lock:
            if self._parts.get((req.rid, s)) is not f.part:
                return                    # torn down, or its partial dropped
            part = f.part
            f.committed = True
            part.rows += f.rows
            expected = self._expected[req.rid]
            count, want_rows = expected[s]
            if part.rows >= want_rows:
                flush = (part, count)
                del self._parts[(req.rid, s)]
                del expected[s]
                if not expected:
                    del self._expected[req.rid]
        if flush is not None:
            self._post(req, s, *flush)

    def _post(self, req: Request, s: int, part: _SegPartial,
              count: int) -> None:
        """The single device->host copy per device per segment, of the
        committed contributions only.  The wait for the partial's fold
        programs is the ``combine_wait`` stage, the copy alone ``copy``."""
        if any(not f.committed for f in part.folds):
            # a withdrawn contribution was folded ahead: rebuild the
            # partial from the committed ones (the slow path)
            acc = None
            for f in part.folds:
                if f.committed:
                    acc = self._fold(req, acc, f.contrib, f.w, s, f.row_lo)
            part.acc = acc
            self.timers.inc("fold_rebuilds")
        with self.timers.stage("combine_wait") as wait:
            if not isinstance(part.acc, np.ndarray):   # fake workers: host
                jax.block_until_ready(part.acc)
        with self.timers.stage("copy") as copy:
            acc = np.asarray(part.acc)
        self.prediction_queue.put(Message(s, None, acc, rid=req.rid,
                                          count=count))
        self.partials_posted += 1
        tr = self.tracer
        if tr is not None and tr.enabled:
            self._tr_ring.append(
                ("X", "post", wait.t0, copy.t1 - wait.t0, req.rid,
                 s, count, None))

    @staticmethod
    def _contribution(req: Request, P, w: float):
        """Member's additive contribution (weighted prediction / vote).  For
        the pallas rule the raw device array passes through: the weighting is
        fused into the accumulate kernel at fold time.  Quantized members
        forward ``(q, per-row scale)`` tuples — pallas defers dequantization
        to the fused epilogue kernel; vote uses ``q`` directly (the per-row
        scale is positive and uniform across classes, so argmax is
        preserved); mean/weighted dequantize here."""
        if isinstance(P, tuple):
            if req.combine == "pallas":
                return P
            from repro.kernels import quant as kq
            P = P[0] if req.combine == "vote" else kq.dequantize(P[0], P[1])
        if req.combine == "vote":
            if isinstance(P, np.ndarray):
                contrib = np.zeros((P.shape[0], req.num_classes), np.float32)
                contrib[np.arange(P.shape[0]), P.argmax(axis=1)] = w
                return contrib
            import jax
            return w * jax.nn.one_hot(P.argmax(axis=-1), req.num_classes,
                                      dtype=np.float32)
        if req.combine == "pallas" and not isinstance(P, np.ndarray):
            return P
        # mean / weighted (and pallas with host arrays from fake workers)
        return P * np.float32(w)

    def _fold(self, req: Request, acc, contrib, w: float, s: int, row_lo: int):
        """Fold a span contribution into the full-segment partial at its row
        offset.  The partial is allocated once per (request, segment) at the
        segment's full row count, host-side while every contribution is a
        host array, else on this combiner's own device.  A host
        contribution (fake workers, an injected fault) is already
        weighted."""
        lo, hi = req.bounds(s)
        seg_rows = hi - lo
        quant = isinstance(contrib, tuple)     # (q, per-row scale) pair
        host = not quant and isinstance(contrib, np.ndarray)
        a = row_lo
        b = row_lo + int(contrib[0].shape[0] if quant else contrib.shape[0])
        if host and (acc is None or isinstance(acc, np.ndarray)):
            if acc is None:
                acc = np.zeros((seg_rows, req.num_classes), np.float32)
            acc[a:b] += contrib                # in-place: no temp per fold
            return acc
        import jax.numpy as jnp
        if acc is None:
            acc = jnp.zeros((seg_rows, req.num_classes), jnp.float32,
                            device=self.device)
        elif isinstance(acc, np.ndarray):      # host folds so far
            acc = jax.device_put(acc, self.device)
        if req.combine == "pallas" and not host:
            from repro.kernels import ops as kops
            w = jnp.full((1,), w, jnp.float32, device=self.device)
            if quant:
                # fused dequant-weight-accumulate epilogue: q stays in its
                # narrow storage dtype all the way into the kernel
                q, scale = contrib
                upd = kops.ensemble_accumulate_quant(
                    acc[a:b], q[None], scale.reshape(1, -1), w)
            else:
                # the accumulate-into-partial Pallas kernel variant, on the
                # span
                upd = kops.ensemble_accumulate(
                    acc[a:b], contrib[None].astype(jnp.float32), w)
            return acc.at[a:b].set(upd) if (a, b) != (0, seg_rows) else upd
        return acc.at[a:b].add(contrib) if (a, b) != (0, seg_rows) \
            else acc + contrib
