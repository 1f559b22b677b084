"""What the engine has dispatched and not yet read, and how it is read.

The step loop (``infer/engine.py``) launches device programs without
waiting for them; each result the host must see goes into ONE queue,
oldest first, as a record of one of two kinds:

- :class:`FirstToken`: a prompt's last chunk went out through the
  standalone chunk program. Its record is queued AHEAD of the decode
  dispatched behind that chunk, so the read blocks until the chunk has
  ended and no longer: the first token is stamped, appended and
  notified one decode step before the pair that used to carry it.
- :class:`StepPair`: one decode, fused mixed or verify step's
  ``[rows, slots]`` pair. ``pipeline_depth`` counts these alone.

:class:`ConsumeLadder` is the consume side, mixed into
``InferenceEngine``: records are read in order, and every token passes
the stale-by-one identity check (the slot still holds the request it
held at dispatch) before it reaches the request. The fields it touches
are the engine's, under the engine's lock, declared again here for
SKY-LOCK (the registry is read a module at a time).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, List, Optional, Sequence

import numpy as np

from skypilot_tpu.utils import failpoints


@dataclasses.dataclass(eq=False)   # a record is itself, not its fields
class FirstToken:
    """``out`` is the chunk program's own ``[token, finite]``
    (``[token]`` with the sentinel off) with its host copy started at
    dispatch; ``req`` held ``slot`` then."""
    out: Any
    slot: int
    req: Any


@dataclasses.dataclass(eq=False)   # a record is itself, not its fields
class StepPair:
    """``out`` is the step's pair, its host copy started at dispatch.
    ``decoded``: the ``(slot, request)`` lanes at dispatch, with the
    draft length as a third member in a verify step, whose ``spec_r``
    is spec_k+1. ``prefilled``: the lanes whose FIRST token is row 0 of
    this pair, which only the fused mixed step has (its chunk and its
    decode are one program, so there is nothing earlier to read)."""
    out: Any
    decoded: List[tuple]
    prefilled: Sequence[tuple] = ()
    spec_r: Optional[int] = None


class Queue(collections.deque):
    """The in-flight records, oldest first. Empty only when both kinds
    are consumed (``idle()``)."""

    def pairs(self) -> int:
        return sum(isinstance(rec, StepPair) for rec in self)


class ConsumeLadder:
    """The consume half of ``InferenceEngine`` (engine thread only)."""

    _GUARDED_BY = {
        '_sched': '_lock',
        '_ttfts': '_lock',
        '_slots': '_lock:mut',
        '_inflight_tok': '_lock:mut',
        '_decode_tokens': '_lock:mut',
        '_spec_slot_steps': '_lock',
        '_spec_drafted': '_lock',
        '_spec_accepted': '_lock',
        '_spec_emitted': '_lock',
        '_sdc_events': '_lock',
        '_model_counters': '_lock',
    }

    def _consume_to(self, pairs: int) -> None:
        """Consume oldest first until at most ``pairs`` step pairs are
        in flight. A first-token record does not count: it is read on
        the way to the pair dispatched behind it, and 0 leaves nothing
        of either kind."""
        while self._queue and (not pairs or self._queue.pairs() > pairs):
            self._consume_one()

    def _drain_inflight(self) -> None:
        """Consume every in-flight record (host state catches up to the
        device). Called before page-pressure decisions."""
        self._consume_to(0)

    def _consume_one(self) -> None:
        """Read back the OLDEST in-flight record and apply its host-side
        bookkeeping (token appends, TTFT stamps, finish detection, slot
        frees). Stale-by-one rule: a slot that no longer holds the
        request it held at dispatch time (finished or preempted since)
        drops its token — for greedy decoding the resume path recomputes
        the identical token, so outputs are depth-invariant."""
        rec = self._queue.popleft()
        # Readback = blocked on the device→host copy; everything after
        # is drain (host bookkeeping catching up). Both accumulate
        # into the current step's record.
        with self._stage('readback'):
            host = np.asarray(rec.out)   # sync point (copy async)
        with self._stage('drain'):
            if isinstance(rec, FirstToken):
                self._apply_first(host, rec)
            else:
                self._apply_pair(host, rec)

    def _sdc_flags(self, flags: 'np.ndarray') -> 'np.ndarray':
        """The sentinel's flags as read (0 = the logits behind that
        token were not all finite), or all zero where the failpoint
        simulates a device NaN on hosts without a corruptible chip."""
        try:
            failpoints.hit('infer.engine.sdc_nan')
        except failpoints.FailpointError:
            return np.zeros_like(flags)
        return flags

    def _apply_first(self, host: 'np.ndarray', rec: FirstToken) -> None:
        """A first-token record: the request's first token, as soon as
        its last chunk has ended. The identity check is all a stale
        record needs: a cancel or a finish empties the slot or hands it
        to another request, and every ``_preempt`` follows a drain or
        finds no slot fully prefilled, so no record outlives a
        preemption."""
        now = time.time()
        slot, req = rec.slot, rec.req
        ok = not self._sentinel or bool(self._sdc_flags(host[1:])[0])
        with self._lock:
            if req.done or self._slots[slot] is not req:
                return   # finished/preempted since dispatch
            if not ok:
                self._sdc_hit(slot, req)
                return
            self._emit_first(slot, req, int(host[0]), now, early=True)
        if not req.done:       # _finish already notified
            req._notify()

    def _emit_first(self, slot: int, req: Any, token: int,  # holds: _lock
                    now: float, early: bool) -> None:
        """Stamp TTFT (once a request: a preemption resume's "first"
        token is just its next) and append the token. A request that
        its first token ends finishes here; its lane in the decode
        already dispatched dies with the slot."""
        if req.first_token_at is None:
            req.first_token_at = now
            self._ttfts.append(now - req.submitted_at)
            self._sched.note_first_token(req, now - req.submitted_at)
            self._sl_first_token(req, now - req.submitted_at, early)
        req.output_tokens.append(token)
        self._decode_tokens += 1
        self._sched.note_tokens(req)
        if self._finished(req, slot, token):
            self._finish(slot, req)

    def _apply_pair(self, pair_host: 'np.ndarray', rec: StepPair) -> None:
        """The host bookkeeping of one consumed step pair."""
        now = time.time()
        bad: set = set()
        if self._sentinel:
            # Sentinel row (appended LAST — all token-row indices are
            # unchanged).
            flags = self._sdc_flags(pair_host[pair_host.shape[0] - 1])
            bad = {s for s in range(flags.shape[0]) if not flags[s]}
        touched: List[Any] = []
        with self._lock:
            # The family's step counts: rows 2.. of a decode pair, the
            # same value in every column (``_decode_paged``).
            for j, name in enumerate(self._step_stats):
                self._model_counters[name] += int(pair_host[2 + j, 0])
            for slot, req in rec.prefilled:
                if req.done or self._slots[slot] is not req:
                    continue   # finished/preempted since dispatch
                if slot in bad:
                    self._sdc_hit(slot, req)
                    continue
                self._emit_first(slot, req, int(pair_host[0, slot]), now,
                                 early=False)
                touched.append(req)
            if rec.spec_r is None:
                for slot, req in rec.decoded:
                    self._inflight_tok[slot] = max(
                        0, self._inflight_tok[slot] - 1)
                    if (req is None or req.done
                            or self._slots[slot] is not req):
                        continue   # stale-by-one: post-finish dropped
                    if slot in bad:
                        # Drop the garbage token; tear the slot down.
                        self._sdc_hit(slot, req)
                        continue
                    token = int(pair_host[1, slot])
                    req.output_tokens.append(token)
                    self._slot_len[slot] += 1
                    self._decode_tokens += 1
                    self._sched.note_tokens(req)
                    touched.append(req)
                    if self._finished(req, slot, token):
                        self._finish(slot, req)
            else:
                self._consume_verify(pair_host, rec.decoded, rec.spec_r,
                                     touched, bad)
        for req in touched:
            if not req.done:       # _finish already notified
                req._notify()

    def _consume_verify(self, pair_host, decoded, spec_r,
                        touched, bad=()) -> None:  # holds: _lock
        """Verify-pair bookkeeping: emit the accepted run plus the
        corrected token ONE token at a time through the exact same
        finish ladder as plain decode — eos / max_tokens / cache_full
        fire mid-run and drop the tail, which is precisely what
        spec-off would have produced — then roll pages extended for
        rejected draft positions back to the pool. ``decoded`` rows
        are (slot, request-at-dispatch, draft_len); ``spec_r`` =
        spec_k+1 (the accepted count sits in pair row spec_r+1)."""
        for slot, req, dl in decoded:
            self._inflight_tok[slot] = max(
                0, self._inflight_tok[slot] - (dl + 1))
            if req is None or req.done or self._slots[slot] is not req:
                continue   # stale-by-one: post-finish tokens dropped
            if slot in bad:
                self._sdc_hit(slot, req)
                continue
            accepted = min(int(pair_host[spec_r + 1, slot]), dl)
            if dl > 0:
                # Only DRAFTING lanes feed the speculation gauges: a
                # draft_len=0 slot co-riding this dispatch (sampled /
                # opted-out / just-prefilled) emits exactly one token
                # like plain decode, and counting it would dilute
                # accepted_len_mean toward 1.0 under mixed traffic —
                # the operator tuning spec_k would read the wrong
                # signal.
                self._spec_slot_steps += 1
                self._spec_drafted += dl
                self._spec_accepted += accepted
                req.spec_steps += 1
            for i in range(accepted + 1):
                token = int(pair_host[1 + i, slot])
                req.output_tokens.append(token)
                self._slot_len[slot] += 1
                self._decode_tokens += 1
                if dl > 0:
                    self._spec_emitted += 1
                    req.spec_emitted += 1
                self._sched.note_tokens(req)
                if self._finished(req, slot, token):
                    self._finish(slot, req)
                    break
            if req.done:
                continue
            touched.append(req)
            if self.allocator is not None:
                # Rejected-draft rollback: pages extended past the new
                # frontier (the next token's write page is kept)
                # return to the pool NOW, not at finish — rejected
                # pages are freed, never leaked (the PR 4 refcount
                # discipline applies, so a somehow-shared page merely
                # loses this slot's reference).
                self.allocator.shrink(slot,
                                      int(self._slot_len[slot]) + 1)

    def _sdc_hit(self, slot: int, req: Any) -> None:  # holds: _lock
        """Non-finite logits observed for a live slot: the garbage
        token is never appended; the request finishes with reason
        'sdc'; the engine flips integrity_suspect (ONE-WAY — the
        server's /health turns 503 "corrupt", admission sheds with the
        quarantined marker, and the control plane's golden-probe loop
        quarantines and replaces the replica). An 'sdc' anomaly dump
        snapshots the flight recorder around the hit."""
        self._sdc_events += 1
        self._integrity_suspect = True
        self._note_anomaly('sdc', {
            'slot': slot, 'request_id': req.request_id,
            'tenant': req.tenant})
        self._finish_early(slot, req, 'sdc')
