"""Faults planted under a cell's timed path, each of which a run's check
must catch: a step that leaves its state unchanged, half of a batch left
out, an answer altered where it is produced.

Each plant takes a patcher with ``setattr(obj, name, value)`` (pytest's
``monkeypatch``, or :class:`Patch`) and replaces one function of the
program.  ``bench/tests/test_run.py`` drives whole runs with each;
``bench/calibrate.py --fault <name>`` reads one at a cell's own size.
"""
from __future__ import annotations

import numpy as np


class Patch:
    """A minimal ``monkeypatch``: ``undo()`` restores what was replaced."""

    def __init__(self):
        self._saved = []

    def setattr(self, obj, name, value):
        self._saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def undo(self):
        for obj, name, value in reversed(self._saved):
            setattr(obj, name, value)
        self._saved.clear()


def fit_state_unchanged(patch):
    from repro.gp import mll

    real = mll._fit_chunk

    def stuck(params, opt_state, *a, **kw):
        _, s, v, traces = real(params, opt_state, *a, **kw)
        return params, s, v, traces

    patch.setattr(mll, "_fit_chunk", stuck)


def fit_half_batch(patch):
    """Half of the observations left out of the fit, the mean over the rest."""
    from repro.gp import mll

    real = mll.fit_hyperparams

    def half(trace_x, mod, y, *a, obs_mask=None, **kw):
        mask = np.ones(y.shape[0], np.float32)
        mask[y.shape[0] // 2:] = 0.0
        return real(trace_x, mod, y, *a, obs_mask=mask, **kw)

    patch.setattr(mll, "fit_hyperparams", half)


def bo_answer_altered(patch):
    from repro.gp import posterior

    real = posterior.pathwise_samples_chunked

    def altered(*a, **kw):
        return real(*a, **kw) * 1.01

    patch.setattr(posterior, "pathwise_samples_chunked", altered)


def bo_half_batch(patch):
    """The draw over half of the nodes only (the rest left at zero)."""
    from repro.gp import posterior

    real = posterior.pathwise_samples_chunked

    def half(*a, **kw):
        out = real(*a, **kw)
        return out.at[out.shape[0] // 2:].set(0.0)

    patch.setattr(posterior, "pathwise_samples_chunked", half)


def bo_refit_stuck(patch):
    """Refits whose Adam steps leave the hyperparameters unchanged."""
    fit_state_unchanged(patch)


def bo_state_unchanged(patch):
    """Rounds that leave the observation buffer as it was."""
    from repro.bo import thompson

    def stuck(state, picks, ys, f_max, checkpoint_cb, t):
        state.iteration = t + 1
        if checkpoint_cb is not None:
            checkpoint_cb(state)

    patch.setattr(thompson, "_record_round", stuck)


def serve_answer_altered(patch):
    from repro.serving import fleet

    real = fleet._engine_step

    def altered(*a, **kw):
        mean, var, draw = real(*a, **kw)
        return mean + 0.01, var, draw

    patch.setattr(fleet, "_engine_step", altered)


def serve_half_batch(patch):
    """Waves that answer only the first half of their slots."""
    from repro.serving import fleet

    real = fleet._engine_step

    def half(state, slot_nodes, key, **kw):
        mean, var, draw = real(state, slot_nodes, key, **kw)
        h = mean.shape[0] // 2
        return mean.at[h:].set(0.0), var.at[h:].set(0.0), draw

    patch.setattr(fleet, "_engine_step", half)


def serve_state_unchanged(patch):
    """Appends that return the state they were given."""
    from repro.serving import update

    patch.setattr(update, "observe_batch_async",
                        lambda state, nodes, ys, donate=True: state)
