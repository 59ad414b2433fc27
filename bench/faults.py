"""Faults planted under the timed path, each of which the check must catch.

``stale`` is the control run on the chip: subscribers are told each
batch's counts one commit late, as a service that deferred its count
reduction into the next batch would tell them. It breaks the guarantee
the configurations state, exact counts at every committed watermark.
The others are the faults the tests plant at a test size: a step that
returns its state unchanged, half of each batch left out, and an answer
altered where it is produced. (A one-chip cell has no exchange between
chips to leave out.)
"""

from __future__ import annotations

import dataclasses

import numpy as np


def _wrap_apply(svc, before=None, after=None):
    backend = svc.backend
    orig = backend.apply_batch

    def apply_batch(delta, want):
        if before is not None:
            delta = before(delta)
        reports = orig(delta, want)
        if after is not None:
            after(reports)
        return reports

    backend.apply_batch = apply_batch


def stale(svc) -> None:
    def after(reports):
        for r in reports.values():
            r.count_after = r.count_before
    _wrap_apply(svc, after=after)


def altered_answer(svc) -> None:
    def after(reports):
        for r in reports.values():
            r.count_after += 1
    _wrap_apply(svc, after=after)


def half_batch(svc) -> None:
    from repro.core.graph import GraphUpdate

    def before(delta):
        u = delta.update
        d, a = np.asarray(u.delete), np.asarray(u.add)
        half = GraphUpdate(delete=d[: (d.shape[0] + 1) // 2], add=a[: (a.shape[0] + 1) // 2])
        return dataclasses.replace(delta, update=half, add_codes=half.add_codes(),
                                   delete_codes=half.delete_codes())
    _wrap_apply(svc, before=before)


def state_unchanged(svc) -> None:
    backend = svc.backend
    orig = backend.storage_step

    def storage_step(pt, add, dele):
        _, diag = orig(pt, add, dele)
        return pt, diag

    backend.storage_step = storage_step


FAULTS = {"stale": stale, "altered_answer": altered_answer,
          "half_batch": half_batch, "state_unchanged": state_unchanged}
