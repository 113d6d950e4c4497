"""Whether what the window produced is right.

Run after the window, once the server and its state are freed, so the
reference never sets the device's peak. Two layers are compared, each
on what the timed path itself returned:

- router: every answered request's expert and coarse score (the
  compiled kernel's score of that expert), against the f32 reference
  scores of its fingerprint under the benchmark's own autoencoder
  weights. ``route_gap`` is the largest relative gap of either kind:
  the served score against the reference score of the served expert,
  or the served expert's reference score against the best one. The
  first is the kernel's rounding; the second is 0 where the router
  chose the reference's expert (or one tied with it within rounding).
- engine: a sample drawn from the seed of the answered requests, the
  longest among them, with every served token teacher-forced through
  the f32 reference of the cell's model family (``served_gaps`` of
  ``bench/families/<family>.py``) over the same zero-padded prompt the
  engine prefilled. ``logit_gap`` is the largest amount by which a served
  token's reference logit lies below the reference's best logit at its
  position. ``short_responses`` counts sampled responses with fewer
  tokens than asked for.

``control=True`` puts the step below the stated precision in the
program's place: coarse scores at ``high`` (three bf16 passes) in
place of the served ones, and the token an fp8 pass puts first in
place of each served token.
"""
from __future__ import annotations

import dataclasses
import gc
from typing import Any, Dict, List

import jax
import numpy as np

from . import matcher_ref, weights


@dataclasses.dataclass
class Sample:
    expert: int
    prompt: np.ndarray
    served: np.ndarray
    padded_len: int
    max_new: int


def collect(sys_, win) -> Dict[str, Any]:
    """Everything the check needs from the window, as host arrays,
    taken before the server is freed."""
    names = sys_.names
    answered = [o for o in win.offers if o.uid in win.responses]
    feats = np.stack([o.features for o in answered]) if answered else \
        np.zeros((0, 784), np.float32)
    served = np.asarray([names.index(win.responses[o.uid].expert)
                         for o in answered], np.int64)
    scores = np.asarray([float(np.asarray(
        win.responses[o.uid].coarse_scores)[0]) for o in answered])
    n_check = int(sys_.cfg["check"]["responses"])
    rng = np.random.default_rng([sys_.seed, 17])
    longest = max(range(len(answered)),
                  key=lambda i: (answered[i].max_new,
                                 len(answered[i].prompt)), default=None)
    pick: List[int] = []
    if longest is not None:
        rest = [i for i in range(len(answered)) if i != longest]
        pick = [longest] + list(rng.choice(
            rest, size=min(n_check - 1, len(rest)), replace=False))
    backend = sys_.server.registry[0].backend
    samples = []
    for i in pick:
        o = answered[i]
        r = win.responses[o.uid]
        samples.append(Sample(
            expert=names.index(r.expert), prompt=o.prompt,
            served=np.asarray(r.tokens, np.int32),
            padded_len=int(backend.pad_shape(1, len(o.prompt))[1]),
            max_new=o.max_new))
    return {"features": feats, "served": served, "scores": scores,
            "samples": samples}


def _router(sys_, got, control: bool) -> Dict[str, float]:
    if not len(got["features"]):
        return {"route_gap": float("inf"), "route_mismatch": 0}
    ref = matcher_ref.bank_scores(sys_.ae_params, sys_.ae_state,
                                  got["features"])
    if control:
        s = matcher_ref.bank_scores(sys_.ae_params, sys_.ae_state,
                                    got["features"], precision="high")
        served, score = s.argmin(-1), s.min(-1)
    else:
        served, score = got["served"], got["scores"]
    at = ref[np.arange(len(ref)), served]
    best = ref.min(-1)
    gap = np.maximum(np.abs(score - at) / at, (at - best) / best)
    return {"route_gap": float(np.max(gap)),
            "route_mismatch": int(np.sum(served != ref.argmin(-1)))}


def _engine(sys_, samples: List[Sample], control: bool
            ) -> Dict[str, float]:
    cfg, fam, a = sys_.cfg, sys_.family, sys_.arch
    n_check = int(cfg["check"]["responses"])
    width = sys_.geometry.max_len
    t_max = n_check * int(sys_.mix["max_new"]["max"])
    short = sum(len(s.served) != s.max_new for s in samples)
    worst, exact, checked = 0.0, 0, 0
    for e in sorted({s.expert for s in samples}):
        group = [s for s in samples if s.expert == e]
        # fixed shapes (n_check rows, max_len wide, t_max tokens), so
        # the reference compiles once per cell and the cache keeps it
        seqs = np.zeros((n_check, width), np.int32)
        spans = []
        for r, s in enumerate(group):
            seqs[r, :len(s.prompt)] = s.prompt
            sb = s.padded_len
            seqs[r, sb:sb + len(s.served) - 1] = s.served[:-1]
            spans.append((r, sb - 1, s.served))
        params = fam.make_expert(weights.expert_key(sys_.seed, e), a)
        gap, hit = fam.served_gaps(params, a, cfg, seqs, spans,
                                   pad_to=t_max, control=control)
        del params
        worst = max(worst, float(np.max(gap, initial=0.0)))
        exact += int(np.sum(hit))
        checked += len(gap)
    return {"logit_gap": worst, "short_responses": float(short),
            "checked_tokens": checked, "exact_argmax": exact}


def readings(sys_, got, *, control: bool = False) -> Dict[str, float]:
    out = _router(sys_, got, control)
    out.update(_engine(sys_, got["samples"], control))
    return out


COMPARED = ("logit_gap", "route_gap", "short_responses")


def verdict(cfg: Dict[str, Any], got: Dict[str, float]):
    """(correct, {name: {"value", "limit"}}) for the compared numbers."""
    limits = cfg["check"]["limits"]
    table = {k: {"value": got[k], "limit": limits[k]} for k in COMPARED}
    ok = all(v["value"] <= v["limit"] for v in table.values())
    return ok, table


def free(sys_) -> None:
    """Drop the server and everything it holds on the device."""
    sys_.server = None
    gc.collect()
    jax.clear_caches()
    gc.collect()
