"""The numbers that decide ``correct``: the timed path's outputs against the
plain reference.

Spikes are binary, so rounding inside a spiking layer shows only where a
membrane lands within rounding of the threshold and one spike flips; the
non-firing readout shows its rounding in every output.  Each output is
therefore held two ways:

Classification answers (``answers``): a served row is *off* when any of its
logits lies more than ``ROW_TOL`` from the reference's.  Float rounding moves
a logit by about 1e-6; one spike that flips moves it by a readout weight
over T, about 1e-3, so ``ROW_TOL`` counts rows touched by a flip:
``rows_off_share`` is their share of the sampled rows.  ``logit_err_median``
is the median row's largest logit error over the median |logit| of the
reference: the readout's own rounding, untouched by the few flipped rows.

Segmentation masks (``masks``): ``mask_px_off_share``, the share of mask
pixels more than ``ROW_TOL`` from the reference; ``mask_err_median``, the
median pixel error over the median |pixel| of the reference; and
``spike_total_gap``, the largest relative gap of a firing layer's spike
total (the non-firing readout's count of ``V >= V_th`` is a few hundred, so
one flip there moves it by half a percent; its pixels are held above).

Training (``training``): each checked step's loss, the first gradient and
the parameters' change over the checked steps.  A gradient or change is
compared leaf by leaf as the gap between the two norms, over the larger of
the reference leaf's norm and the median leaf's; the worst leaf counts.
Leaves whose reference gradient norm is under a thousandth of the median
leaf's move by round-off alone and are left out.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

ROW_TOL = 1e-4
NEGLIGIBLE = 1e-3


def answers(got: np.ndarray, want: np.ndarray) -> Dict[str, float]:
    """Share of rows (first axis) with a logit more than ``ROW_TOL`` off."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return {"rows_off_share": 1.0, "logit_err_median": float("inf")}
    diff = np.abs(got - want).reshape(got.shape[0], -1).max(axis=1)
    scale = max(float(np.median(np.abs(want))), 1e-30)
    return {"rows_off_share": float(np.mean(diff > ROW_TOL)),
            "logit_err_median": float(np.median(diff)) / scale}


def masks(got: np.ndarray, want: np.ndarray, got_totals: Sequence[float],
          want_totals: Sequence[float]) -> Dict[str, float]:
    """Share of mask pixels off, the median pixel error, and the worst gap
    of the spike totals given (the firing layers')."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        px, err = 1.0, float("inf")
    else:
        diff = np.abs(got - want)
        px = float(np.mean(diff > ROW_TOL))
        err = float(np.median(diff)) / max(float(np.median(np.abs(want))),
                                           1e-30)
    gaps = [abs(float(g) - float(w)) / max(abs(float(w)), 1.0)
            for g, w in zip(got_totals, want_totals)]
    if len(got_totals) != len(want_totals):
        gaps.append(1.0)
    return {"mask_px_off_share": px, "mask_err_median": err,
            "spike_total_gap": max(gaps)}


def _leaves(tree) -> List[np.ndarray]:
    if isinstance(tree, dict):
        return [l for k in sorted(tree) for l in _leaves(tree[k])]
    if isinstance(tree, (list, tuple)):
        return [l for v in tree for l in _leaves(v)]
    return [np.asarray(tree, np.float64)]


def norm_gap(got, want, mask: Sequence[bool]) -> float:
    """Worst leaf's gap of norms, over the larger of the reference leaf's
    norm and the median leaf's."""
    g = [float(np.linalg.norm(a)) for a in _leaves(got)]
    w = [float(np.linalg.norm(a)) for a in _leaves(want)]
    if len(g) != len(w):
        return float("inf")
    kept = [(a, b) for a, b, m in zip(g, w, mask) if m]
    med = float(np.median([b for _, b in kept]))
    return max(abs(a - b) / max(b, med) for a, b in kept)


def training(losses: Sequence[float], grad1, params0, params_n,
             ref) -> Dict[str, float]:
    """``ref`` is ``reference.sgd_steps`` over the same checked steps."""
    ref_losses = [r[0] for r in ref]
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses, ref_losses))
    if not all(np.isfinite(losses)):
        loss_gap = float("inf")
    ref_g1 = ref[0][1]
    norms = [float(np.linalg.norm(a)) for a in _leaves(ref_g1)]
    med = float(np.median(norms))
    mask = [n >= NEGLIGIBLE * med for n in norms]
    delta = [a - b for a, b in zip(_leaves(params_n), _leaves(params0))]
    ref_delta = [a - b for a, b in zip(_leaves(ref[-1][2]),
                                       _leaves(params0))]
    return {"loss_gap": float(loss_gap),
            "grad_gap": norm_gap(grad1, ref_g1, mask),
            "update_gap": norm_gap(delta, ref_delta, mask)}
