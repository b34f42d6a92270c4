"""Evaluation metrics more than one model directory of the zoo reports
(a model directory imports none of another's: tests/test_zoo.py)."""

from __future__ import annotations

import numpy as np


def auc(outputs, labels):
    """Area under the ROC curve by ranks (Mann-Whitney); 0.5 where the
    labels hold one class."""
    order = np.argsort(outputs)
    ranks = np.empty_like(order, dtype=np.float64)
    ranks[order] = np.arange(1, len(outputs) + 1)
    pos = labels.astype(bool)
    n_pos = int(pos.sum())
    n_neg = len(labels) - n_pos
    if n_pos == 0 or n_neg == 0:
        return 0.5
    return float(
        (ranks[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg)
    )
