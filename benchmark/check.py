"""The comparison that decides `correct` for a family that brings none of its
own (`reference/<family>.py` `compare`): the served class probabilities of a
seeded sample against the family's plain reference.

The statistic is ONE number over the whole sample, not a maximum: the RMS of
the difference between served and reference centred log-probabilities
(log p minus its mean over the classes, which is the logit minus its mean:
the server answers probabilities only, and softmax loses just the common
shift). An RMS over a few hundred values is steady from seed to seed, which a
maximum is not, and int8 arithmetic is only a few times coarser than
bfloat16, so the limit has little room (reference/<family>.py states it with
both readings)."""

from __future__ import annotations

import numpy as np


def probs_by_class(answer: dict, n_classes: int) -> np.ndarray:
    """One text's answer {"top_k": [{"class", "prob"}, ...]} -> (n_classes,)
    probabilities by class index. Every class must be there once."""
    out = np.full(n_classes, np.nan)
    for e in answer["top_k"]:
        out[int(e["class"])] = float(e["prob"])
    if np.isnan(out).any() or len(answer["top_k"]) != n_classes:
        raise ValueError(f"answer does not hold every class once: {answer}")
    return out


def centred(logp: np.ndarray) -> np.ndarray:
    return logp - logp.mean(axis=-1, keepdims=True)


def rms_centred_logit_error(served_probs: np.ndarray, ref_logp: np.ndarray) -> float:
    served = np.asarray(served_probs, np.float64)
    if not np.isfinite(served).all() or (served <= 0).any():
        return float("inf")
    diff = centred(np.log(served)) - centred(np.asarray(ref_logp, np.float64))
    return float(np.sqrt(np.mean(np.square(diff))))


def between_texts_rms(ref_logp: np.ndarray) -> float:
    """How far apart the reference's own answers are from text to text (RMS of
    the centred logits about their mean over the texts): a sample whose texts
    all answer alike could not show a swapped or repeated lane."""
    c = centred(np.asarray(ref_logp, np.float64))
    return float(np.sqrt(np.mean(np.square(c - c.mean(axis=0, keepdims=True)))))


def compare_class_probs(served: list, ref_logp: np.ndarray, cfg: dict) -> tuple[float, str]:
    """What a family that gives no `compare` of its own is held to: the served
    answers' class probabilities against the reference's log-probabilities by
    `rms_centred_logit_error`. Returns the statistic and the line a run prints
    beside its limit."""
    n_classes = int(cfg["assumed"]["num_classes"])
    probs = np.stack([probs_by_class(a, n_classes) for a in served])
    stat = rms_centred_logit_error(probs, ref_logp)
    apart = between_texts_rms(ref_logp)
    return stat, (f"rms_centred_logit_error={stat:.6g} over {len(served)} texts x {n_classes} "
                  f"classes (the reference's texts answer {apart:.4g} apart, so a swapped "
                  f"lane reads about {apart * 2 ** 0.5:.4g})")
