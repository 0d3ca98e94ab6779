"""A fit cell's run, chip check skipped, at a small size: sound, it is
correct; with the timed path broken underneath, ``correct`` comes out false.
One chip: no exchange between chips to leave out."""

import numpy as np
import pytest

from bench import deploy
from bench.tests.small import rebuilt_program, run

CELLS = ["appendix_c-fit", "credit-fit"]


@pytest.fixture(params=CELLS)
def cell(request):
    return request.param


def test_sound_fit_run_is_correct(cell):
    with rebuilt_program():
        result = run(cell)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1


def test_step_that_returns_its_state_unchanged(cell, monkeypatch):
    deploy.use_program()
    from repro.core import ihb

    monkeypatch.setattr(ihb, "append_column", lambda state, *a, **k: state)
    with rebuilt_program():
        result = run(cell)
    assert result["correct"] is False


def test_half_the_rows_left_out_mean_over_the_rest(cell, monkeypatch):
    deploy.use_program()
    from repro.kernels import ops

    whole = ops.gram_accumulate

    def half(A, X, parents, vars_, acc=None, **kw):
        h = A.shape[0] // 2
        QL, C = whole(A[:h], X[:h], parents, vars_, acc, **kw)
        return 2.0 * QL, 2.0 * C

    monkeypatch.setattr(ops, "gram_accumulate", half)
    with rebuilt_program():
        result = run(cell)
    assert result["correct"] is False


def test_answer_altered_where_it_is_produced(cell, monkeypatch):
    deploy.use_program()
    from repro.core import class_batch

    collect = class_batch.collect_degree

    def altered(book, border, accepted, mses, coeffs, generators):
        ell = collect(book, border, accepted, mses, coeffs, generators)
        if generators:  # one coefficient of the newest generator, 0.01 off
            coeffs = generators[-1].coeffs.copy()
            coeffs[0] += 0.01
            generators[-1] = generators[-1]._replace(coeffs=coeffs)
        return ell

    monkeypatch.setattr(class_batch, "collect_degree", altered)
    with rebuilt_program():
        result = run(cell)
    assert result["correct"] is False


def test_svm_that_never_moves(cell, monkeypatch):
    """The SVM head's step returns its starting state: W = 0, b = 0."""
    deploy.use_program()
    from repro.core import svm

    def unmoved(self, X, y):
        X = np.asarray(X)
        self.classes_ = np.unique(np.asarray(y))
        self.W = np.zeros((X.shape[1], len(self.classes_)), np.float32)
        self.b = np.zeros((len(self.classes_),), np.float32)
        self.stats = {"iters": 0, "nnz": 0}
        return self

    monkeypatch.setattr(svm.LinearSVM, "fit", unmoved)
    with rebuilt_program():
        result = run(cell)
    assert result["correct"] is False
    assert result["checks"]["score_gap"]["value"] > result["checks"]["score_gap"]["limit"]


def test_svm_on_half_the_rows(cell, monkeypatch):
    """The SVM head trained on the first half of the rows, its loss the mean
    over those."""
    deploy.use_program()
    from repro.core import svm

    whole = svm.LinearSVM.fit

    def half(self, X, y):
        h = np.asarray(X).shape[0] // 2
        return whole(self, np.asarray(X)[:h], np.asarray(y)[:h])

    monkeypatch.setattr(svm.LinearSVM, "fit", half)
    with rebuilt_program():
        result = run(cell)
    assert result["correct"] is False
