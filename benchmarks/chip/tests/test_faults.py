"""A whole run, with the program broken underneath, comes out not correct:
one run for each fault the cells can have.  (The cells run on one chip, so
there is no exchange between chips to leave out.)"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from conftest import TINY_REPLAY, TINY_SWEEP


def _answer_altered_sweep(monkeypatch):
    import repro.dse.fused as fused

    real = fused.images_per_sec
    monkeypatch.setattr(fused, "images_per_sec", lambda *a: real(*a) * (1 + 1e-6))


def _half_left_out_sweep(monkeypatch):
    import repro.dse as dse

    real = dse.run_fused_sweep

    def half(points, **kw):
        h = len(points) // 2
        res = real(points[:h], **kw)
        cols = {}
        for c in ("total_cycles", "images_per_sec", "mean_utilization", "arrays_used", "arrays_total"):
            v = getattr(res, c)
            cols[c] = np.concatenate([v, np.full(len(points) - h, v.mean()).astype(v.dtype)])
        return dataclasses.replace(res, points=list(points), **cols)

    monkeypatch.setattr(dse, "run_fused_sweep", half)


def _state_unchanged_sweep(monkeypatch):
    from repro.core.alloc.greedy import GreedyEventSchedule

    real = GreedyEventSchedule.replicas_at

    def unchanged(self, budgets):
        res = real(self, budgets)
        return dataclasses.replace(res, replicas=np.ones_like(res.replicas))

    monkeypatch.setattr(GreedyEventSchedule, "replicas_at", unchanged)


def _capture_altered(monkeypatch):
    """One first-layer sample off by one bit where the capture makes it."""
    import repro.dse.sweep as sweep

    real = sweep.capture_activations

    def altered(*a, **kw):
        cap = real(*a, **kw)
        q = cap.layers[0].sampled_q.copy()
        q[0, 0] ^= 1
        layer = dataclasses.replace(cap.layers[0], sampled_q=q)
        return dataclasses.replace(cap, layers=(layer,) + tuple(cap.layers[1:]))

    monkeypatch.setattr(sweep, "capture_activations", altered)
    monkeypatch.setattr(sweep, "_CAPTURE_CACHE", {})
    monkeypatch.setattr(sweep, "_PROFILE_CACHE", {})


def _patch_run_batch(monkeypatch, edit):
    from repro.fabric import VirtualTimeFabric

    real = VirtualTimeFabric.run_batch
    monkeypatch.setattr(VirtualTimeFabric, "run_batch",
                        lambda self, allocs, procs, **kw: edit(real, self, allocs, procs, **kw))


def _answer_altered_replay(monkeypatch):
    def edit(real, self, allocs, procs, **kw):
        res = real(self, allocs, procs, **kw)
        comp = res.completions.copy()
        comp[-1, -1] += 1.0
        return dataclasses.replace(res, completions=comp)

    _patch_run_batch(monkeypatch, edit)


def _half_left_out_replay(monkeypatch):
    def edit(real, self, allocs, procs, **kw):
        h = len(allocs) // 2
        res = real(self, allocs[:h], procs[:h], **kw)
        fill = lambda a: np.concatenate([a, np.repeat(a.mean(axis=0, keepdims=True), len(allocs) - h, 0)])  # noqa: E731
        return dataclasses.replace(res, arrivals=fill(res.arrivals), completions=fill(res.completions),
                                   percentiles=fill(res.percentiles))

    _patch_run_batch(monkeypatch, edit)


def _state_unchanged_replay(monkeypatch):
    import repro.fabric.vtime as vtime

    real = vtime.pool_dispatch

    def unchanged(xp, scan, free, t_ready, svc, b_mask, collect=False):
        out = real(xp, scan, free, t_ready, svc, b_mask, collect)
        return (free,) + tuple(out[1:])

    monkeypatch.setattr(vtime, "pool_dispatch", unchanged)


@pytest.mark.parametrize(
    "mix, fault",
    [
        (TINY_SWEEP, _answer_altered_sweep),
        (TINY_SWEEP, _half_left_out_sweep),
        (TINY_SWEEP, _state_unchanged_sweep),
        (TINY_REPLAY, _answer_altered_replay),
        (TINY_REPLAY, _half_left_out_replay),
        (TINY_REPLAY, _state_unchanged_replay),
        (TINY_SWEEP, _capture_altered),
        (TINY_REPLAY, _capture_altered),
    ],
    ids=["sweep-answer-altered", "sweep-half-left-out", "sweep-state-unchanged",
         "replay-answer-altered", "replay-half-left-out", "replay-state-unchanged",
         "sweep-capture-altered", "replay-capture-altered"],
)
def test_a_broken_program_is_not_correct(checkout, monkeypatch, mix, fault):
    mod = checkout({"tiny": mix})
    fault(monkeypatch)
    result, _ = mod.run("vgg11.tiny", 424242, 0.0, False)
    assert result["correct"] is False, result["checks"]
