"""Whether what the window served is right: the comparison that decides
``correct``.

Once the window has closed, a sample of the requests due in it, drawn from
the seed, is answered again by the plain reference (``reference.py``) from
the same window of T3 data.  Each answer is held to it:

- ``masks_off``: requests whose candidate count or members disagree with the
  reference's filter mask (exact, limit 0);
- ``pools_off_pct``: share of requests whose pool differs from the
  reference's Algorithm 1 pool in members, their order, counts or hourly
  cost;
- ``score_gap``: the widest gap between a served member's combined,
  availability or cost score and the reference's (score points, 0-110);
- ``unanswered``: sampled requests that failed or never resolved (limit 0);
- ``versions_off`` (a live deployment): answers from an archive version
  other than the newest when their drain took its snapshot (limit 0; see
  ``versions_off``).

A live deployment's answers are each held to the window of the version
that served them, as its integer tier stores it (``reference.TierWindows``).

The limits sit in the configuration's file under ``limits``; PERF.md gives
the readings each was set from.  The reference's answer to a request
depends only on its filters, axis, amount, weight and lambda, so equal
requests are answered once.
"""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from bench import reference


def draw_sample(n: int, size: int, seed: int, amounts) -> list:
    """Indices of the requests to check, drawn from the seed; the request
    with the largest amount is always in."""
    rng = np.random.default_rng([seed, 6])
    pick = rng.choice(n, min(size, n), replace=False)
    return sorted(int(i) for i in np.union1d(pick, [np.argmax(amounts)]))


def as_served(ans: reference.Answer, cands) -> SimpleNamespace:
    """A reference answer in the shape of the program's Recommendation (the
    control stands in the program's place this way)."""
    m = ans.members
    return SimpleNamespace(
        names=cands.names[m], azs=cands.azs[m], counts=ans.counts,
        combined=ans.combined[m], availability=ans.availability[m],
        cost=ans.cost[m], hourly_cost=ans.hourly,
        diagnostics={"candidates_considered": ans.considered})


class Answers:
    """The reference's answers over one deployment's window, at one
    precision, each distinct request answered once."""

    def __init__(self, dep, precision: str = "float64"):
        self.cands = dep.cands
        self.precision = precision
        self.stats = reference.window_stats(dep.cands.t3, precision)
        self._memo: dict = {}

    def __call__(self, req) -> reference.Answer:
        key = reference.request_key(req)
        if key not in self._memo:
            self._memo[key] = reference.answer(req, self.cands, self.stats,
                                               self.precision)
        return self._memo[key]


class VersionAnswers:
    """The reference's answers over a rolling integer tier, each from the
    window of the version asked for (versions in increasing order), each
    distinct request answered once per version."""

    BITS = {"int8": 8, "int4": 4}

    def __init__(self, cfg: dict, dep, precision: str | None = None):
        arc = cfg["archive"]
        self.cands = dep.cands
        self.windows = reference.TierWindows(
            dep.cands.t3, dep.ticks, float(arc["headroom"]),
            self.BITS[precision or arc["precision"]])
        self._memo: dict = {}
        self._at = None

    def __call__(self, req, version: int) -> reference.Answer:
        if version != self._at:
            self._memo, self._at = {}, version
            self._stats = self.windows.stats(version)
        key = reference.request_key(req)
        if key not in self._memo:
            self._memo[key] = reference.answer(req, self.cands, self._stats)
        return self._memo[key]


def _answers(cfg: dict, dep, versions, precision: str | None = None):
    """``ref(request, version)`` over the configuration's archive."""
    if versions is None:
        ref = Answers(dep, precision or "float64")
        return lambda req, _version: ref(req)
    return VersionAnswers(cfg, dep, precision)


def _by_version(served: list, versions) -> list:
    """``served`` rows with their version appended, in version order."""
    if versions is None:
        return [(*s, None) for s in served]
    rows = [(*s, v) for s, v in zip(served, versions)]
    return sorted(rows, key=lambda r: -1 if r[-1] is None else r[-1])


def control_answers(cfg: dict, dep, served: list, precision: str,
                    versions=None) -> list:
    """``served`` with each answer replaced by the control's: the reference
    at ``precision`` (``bfloat16`` for a float32 archive; ``int4`` for an
    int8 tier, from the window of the version that served each) in the
    program's place."""
    ref = _answers(cfg, dep, versions, precision)
    out = [(req, None, None) for req, _, _ in served]
    for req, _, _, i, v in _by_version(
            [(*s, i) for i, s in enumerate(served)], versions):
        if versions is None or v is not None:
            out[i] = (req, as_served(ref(req, v), dep.cands), None)
    return out


def versions_off(served: list, published: dict, began: dict) -> int:
    """Answers not from the archive version current when their drain took
    its snapshot.

    ``served``: per answer, ``(version the drain served, version stamped on
    the answer or None where not kept, when the snapshot began, when it
    ended)``; ``began`` and ``published``: per version, when the poll that
    made it began and when it returned.  An answer is off if its stamp
    differs from the version served, if that version was never published
    or its poll began after the snapshot ended, or if a later version had
    been published before the snapshot began.  Only a tick whose poll
    overlaps the snapshot leaves a choice between two versions.
    """
    off = 0
    for version, stamp, s0, s1 in served:
        later = published.get(version + 1)
        off += ((stamp is not None and stamp != version)
                or version not in began or began[version] > s1
                or (later is not None and later < s0))
    return off


def compare(cfg: dict, dep, served: list, versions=None) -> dict:
    """``{name: (value, limit)}`` for the sampled answers.

    ``served``: ``(request, recommendation, error)`` per sampled request;
    ``versions``, for a live deployment: the archive version that served
    each (None where none did).
    """
    cands = dep.cands
    where = {(n, z): i for i, (n, z) in enumerate(zip(cands.names,
                                                      cands.azs))}
    ref_of = _answers(cfg, dep, versions)
    off = {"masks_off": 0, "pools_off": 0, "unanswered": 0}
    gap = 0.0
    answered = 0
    for req, rec, err, version in _by_version(served, versions):
        if err is not None or rec is None or (versions is not None
                                              and version is None):
            off["unanswered"] += 1
            continue
        answered += 1
        ref = ref_of(req, version)
        idx = np.array([where.get((n, z), -1)
                        for n, z in zip(rec.names, rec.azs)], np.int64)
        inside = (idx >= 0) & ref.mask[np.maximum(idx, 0)]
        if (rec.diagnostics.get("candidates_considered") != ref.considered
                or not inside.all()):
            off["masks_off"] += 1
        same = (len(idx) == len(ref.members)
                and np.array_equal(idx, ref.members)
                and np.array_equal(np.asarray(rec.counts), ref.counts)
                and abs(rec.hourly_cost - ref.hourly)
                <= 1e-9 * max(abs(ref.hourly), 1.0))
        off["pools_off"] += not same
        j = idx[inside]
        for got, want in ((rec.combined, ref.combined),
                          (rec.availability, ref.availability),
                          (rec.cost, ref.cost)):
            if len(j):
                d = np.abs(np.asarray(got, np.float64)[inside] - want[j])
                gap = max(gap, float(d.max()))
    limits = cfg["limits"]
    lim = lambda k: float(limits[k])  # noqa: E731
    return {"score_gap": (gap, lim("score_gap")),
            "pools_off_pct": (100.0 * off["pools_off"] / max(answered, 1),
                              lim("pools_off_pct")),
            "masks_off": (float(off["masks_off"]), lim("masks_off")),
            "unanswered": (float(off["unanswered"]), lim("unanswered"))}
