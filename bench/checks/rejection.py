"""The comparison that decides ``correct`` for a rejection pool.

An answer says three things: the items, that they are an accepted
proposal, and that it was the first one accepted (its trial count).  Every
draw the window served (or a sample of ``max_draws`` of them, drawn from
the run's seed) is replayed by the reference with its served items forced
(``reference.Reference.judge``), and a sample of ``rejected_replays`` of
the proposals the answers say were rejected is drawn by the reference
itself (``Reference.replay``), at the cell's own catalog.  The numbers
compared:

- ``path_gap``: how far, in probability, a served eigenvector coin or
  tree-descent decision lies on the wrong side of the reference's
  threshold, widest over the draws (the descent kernel);
- ``leaf_gap``: how far, in nats, a served leaf item falls short of the
  reference's Gumbel-max winner, widest over the draws (leaf scoring);
- ``accept_gap``: log u minus the reference's log acceptance ratio of a
  served draw, in nats, widest over the draws (the 2K-space log-det and
  acceptance test: above 0, the reference rejects what was served);
- ``early_accepts``: how many of the replayed rejected proposals the
  reference accepts by more than the ``accept_gap`` limit (a proposal
  passed over, or a trial count that is too high);
- ``trials_z``: the mean trials of every answered request against the
  reference's exact E[trials], in standard errors of a mean of geometric
  counts (a trial count inflated across the board).

The limits are in ``rejection.json``; ``PERF.md`` gives the readings they
were set from.
"""
from __future__ import annotations

import json
import math
import time
from pathlib import Path

import numpy as np

from reference import Reference

LIMITS = json.loads(Path(__file__).with_suffix(".json").read_text())


def _rejected(answered, n: int, rng):
    """(seeds, trial indices) of a sample of ``n`` proposals that answers
    say were rejected, drawn without replacement over all of them."""
    seeds = np.array([r.seed for r in answered], np.int64)
    counts = np.array([r.trials - 1 if r.accepted else r.trials
                       for r in answered], np.int64)
    total = int(counts.sum())
    if total == 0:
        return seeds[:0], counts[:0]
    pick = np.sort(rng.choice(total, min(n, total), replace=False))
    ends = np.cumsum(counts)
    req = np.searchsorted(ends, pick, side="right")
    return seeds[req], pick - (ends[req] - counts[req])


def check(requests, *, catalog, facts, seed, log):
    """(numbers {name: (value, limit)}, facts found) for one run."""
    import jax

    t_ref = time.perf_counter()
    limits = LIMITS["limits"]
    answered = [r for r in requests if r.done is not None]
    drawn = [r for r in answered if r.accepted and r.items is not None]
    rng = np.random.default_rng([int(seed), 21])
    cap = int(LIMITS["max_draws"])
    if len(drawn) > cap:
        drawn = [drawn[i] for i in sorted(rng.choice(len(drawn), cap,
                                                     replace=False))]
    v, b, d = (np.asarray(jax.device_get(x)) for x in catalog)
    ref = Reference(v, b, d, facts["block"])
    e_trials = ref.expected_trials()
    numbers = {}
    if drawn:
        gaps = ref.judge([r.seed for r in drawn], [r.trials for r in drawn],
                         np.stack([r.items for r in drawn]))
        for name in ("path_gap", "leaf_gap", "accept_gap"):
            numbers[name] = float(np.max(gaps[name]))
        i = int(np.argmax(gaps["leaf_gap"]))
        log(f"widest leaf gap at item step {int(gaps['leaf_step'][i])} of "
            f"{int(gaps['size'][i])}; leaf gaps by draw, largest first: "
            f"{np.round(np.sort(gaps['leaf_gap'])[::-1][:8], 4).tolist()}")
        again = ref.replay([r.seed for r in drawn],
                           [r.trials - 1 for r in drawn])["items"]
        same = sum(set(a[a >= 0].tolist()) == set(r.items[r.items >= 0].tolist())
                   for a, r in zip(again, drawn))
        log(f"the reference's own draw of the accepted proposal is the "
            f"served one in {same} of {len(drawn)} draws")
    else:
        numbers.update(path_gap=math.inf, leaf_gap=math.inf,
                       accept_gap=math.inf)
    if answered:
        seeds, ts = _rejected(answered, int(LIMITS["rejected_replays"]), rng)
        margin = ref.replay(seeds, ts)["margin"] if len(ts) else np.zeros(0)
        early = margin < -float(limits["accept_gap"])
        numbers["early_accepts"] = float(np.sum(early))
        log(f"{len(ts)} rejected proposals replayed: the reference accepts "
            f"{int(early.sum())} (trial indices {ts[early].tolist()[:8]}); "
            f"least margin {float(np.min(margin, initial=np.inf)):.4f} nats")
        trials = np.array([r.trials for r in answered], np.float64)
        sem = math.sqrt(e_trials * max(e_trials - 1.0, 0.0) / len(trials))
        numbers["trials_z"] = (float(trials.mean()) - e_trials) / sem
        mean_trials = float(trials.mean())
    else:
        numbers.update(early_accepts=math.inf, trials_z=math.inf)
        mean_trials = math.nan
    log(f"reference: E[trials] {e_trials:.3f}, E|Y| {ref.expected_size():.3f}; "
        f"{len(drawn)} draws judged; mean trials {mean_trials:.1f} over "
        f"{len(answered)} answered; {time.perf_counter() - t_ref:.1f} s")
    out = {k: (v, float(limits[k])) for k, v in numbers.items()}
    return out, {"e_trials": e_trials, "e_size": ref.expected_size()}
