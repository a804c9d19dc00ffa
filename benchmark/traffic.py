"""The one general traffic generator. A traffic mix is a data file of
parameters under ``benchmark/traffic/``; this reads it and makes the
list of requests from the seed. The seed sets the draw (which hosts,
which rack) and the order, never the counts: every seed gives the same
number of requests of each template, with the same shapes.

A file has ``loop`` (``closed``: ``clients`` callers, each sending its
next request when the last is answered; ``open``: arrivals on a
schedule at ``rate_per_s`` over ``clients`` keep-alive connections),
``timeout_s``, ``warmup_per_template`` and ``requests``: templates with
a ``share`` of the traffic, a ``method``, a ``path`` and a JSON
``body`` in which ``$start_ms``, ``$end_ms`` and ``$metric`` stand for
the deployment's span and metric, and ``$<name>`` for a draw. A draw
names a ``tag``, a ``range`` of that tag's values (``[lo, hi)`` or
``"all"``) and how many to ``pick``: one value at a time from a
seeded permutation of the range, or several distinct ones joined with
``|``, no set of them drawn twice in a list. A draw of one value that
says ``"repeat": true`` is drawn with
replacement instead: a few panels asked again and again, which the
result cache may answer; one that does not say so is refused where the
list needs more values than the range has. ``trace_probe`` is one more
template, of which a traced run sends three drawn requests: two to
warm it and one at the end of the traced stretch (see ``run.py``).

A template may carry a ``window``: ``length_s``, ``step_ms``,
``jitter_ms`` and ``"order": "ascending"``. Its requests then ask a
window of their own instead of the deployment's span, the window of a
dashboard whose "now" moves: request ``i`` of the template, the
warm-up's first, gets

    end_ms   = first_end_ms + i * step_ms + jitter_i
    start_ms = end_ms - 1000 * length_s

where ``first_end_ms`` is ``1000 * (data.t0 + length_s)`` and
``jitter_i`` a seeded draw in ``[1, jitter_ms)`` (0 where ``jitter_ms``
is under 2), and these fill ``$start_ms`` and ``$end_ms``. A "now" does
not jump back: the timed list is shuffled as ever, and the requests of
a template with a window then take the places the shuffle gave their
template in ascending order, so a file may mix such a template with
others and each keeps its relative order. A list whose last window
ends after the data does (``data.end``) is refused. A template without
a window draws, fills and shuffles as it always did and takes the same
numbers from the seed in the same order.

A closed loop sends as many requests as the server answers, so its
list has to outlast any window: ``closed_list`` is its length, warm-up
included, 2,000 where a file does not say (25.5 ms a request over
51 s). ``loadgen.py`` raises when a window uses the list up.

``writes``, where a file has it, is a schedule of ``/api/put`` bodies
beside the queries, open loop whatever the queries' loop is:
``rate_per_s`` bodies a second, evenly spaced, each with one new point
for ``series_per_body`` series, block after block through the
deployment and then on to the next timestamp, at the deployment's own
cadence from the end of its history. The seed sets the values.
"""

from __future__ import annotations

import json
import math

import numpy as np


class Request:
    __slots__ = ("template", "method", "path", "body", "doc", "due_s")

    def __init__(self, template: str, method: str, path: str,
                 doc: dict | None, due_s: float | None = None):
        self.template = template
        self.method = method
        self.path = path
        self.doc = doc
        self.body = json.dumps(doc, separators=(",", ":")).encode() \
            if doc is not None else b""
        self.due_s = due_s


def _fill(node, env: dict):
    if isinstance(node, dict):
        return {k: _fill(v, env) for k, v in node.items()}
    if isinstance(node, list):
        return [_fill(v, env) for v in node]
    if isinstance(node, str) and node.startswith("$"):
        return env[node[1:]]
    return node


def _draws(spec: dict, data, n: int, rng) -> list[str]:
    """``n`` values of one draw, as the strings that go in a filter."""
    tag = spec["tag"]
    lo, hi = (0, data.tag_count(tag)) if spec["range"] == "all" \
        else spec["range"]
    pick = int(spec["pick"])
    if pick == 1 and spec.get("repeat"):
        ids = rng.integers(lo, hi, size=(n, 1))
    elif pick == 1:
        if n > hi - lo:
            raise ValueError(
                f"traffic needs {n} distinct {tag} values and the file "
                f"lists {hi - lo}: a repeated request would be "
                f"answered from the result cache")
        ids = lo + rng.permutation(hi - lo)[:n, None]
    else:
        if math.comb(hi - lo, pick) < n:
            raise ValueError(
                f"traffic needs {n} distinct sets of {pick} {tag} "
                f"values and the file's range has "
                f"{math.comb(hi - lo, pick)}: a repeated request would "
                f"be answered from the result cache")
        ids = rng.integers(lo, hi, size=(n, pick))
        while True:
            # distinct within a row, and no row's set drawn before
            srt = np.sort(ids, axis=1)
            dup = (srt[:, 1:] == srt[:, :-1]).any(axis=1)
            _sets, first = np.unique(srt, axis=0, return_index=True)
            again = np.ones(n, dtype=bool)
            again[first] = False
            dup |= again
            if not dup.any():
                break
            ids[dup] = rng.integers(lo, hi, size=(int(dup.sum()), pick))
    return ["|".join(data.tag_name(tag, int(i)) for i in row)
            for row in ids]


def _windows(w: dict, data, n: int, rng) -> dict:
    """``start_ms`` and ``end_ms`` of the ``n`` requests of a template
    with a window, in the template's order."""
    if w.get("order") != "ascending":
        raise ValueError(f"window order {w.get('order')!r}: a \"now\" "
                         f"moves forward (\"ascending\")")
    length_ms, step_ms = 1000 * int(w["length_s"]), int(w["step_ms"])
    jitter_ms = int(w.get("jitter_ms", 0))
    if length_ms <= 0 or step_ms < max(jitter_ms, 1):
        raise ValueError("a window has a length, and steps no shorter "
                         "than its jitter: it never moves back")
    jitter = rng.integers(1, jitter_ms, size=n) if jitter_ms > 1 \
        else np.zeros(n, dtype=np.int64)
    end = 1000 * data.t0 + length_ms + step_ms * np.arange(n) + jitter
    if n and int(end[-1]) // 1000 > data.end:
        raise ValueError(
            f"the list's last window ends at {int(end[-1])} ms and the "
            f"data at {data.end} s: {n} requests of {step_ms} ms after "
            f"{w['length_s']} s do not fit {data.points} points")
    return {"start_ms": (end - length_ms).tolist(),
            "end_ms": end.tolist()}


def _template_requests(tpl: dict, data, n: int, rng) -> list[Request]:
    env_base = {"start_ms": data.t0 * 1000, "end_ms": data.end * 1000,
                "metric": data.metric}
    drawn = {name: _draws(spec, data, n, rng)
             for name, spec in sorted((tpl.get("draw") or {}).items())}
    if tpl.get("window"):
        drawn.update(_windows(tpl["window"], data, n, rng))
    out = []
    for i in range(n):
        env = dict(env_base, **{k: v[i] for k, v in drawn.items()})
        doc = _fill(tpl["body"], env) if tpl.get("body") is not None \
            else None
        out.append(Request(tpl["name"], tpl["method"], tpl["path"],
                           doc))
    return out


class Traffic:
    def __init__(self, spec: dict, data, seed: int, seconds: float,
                 closed_max: int = 2000):
        self.loop = spec["loop"]
        if self.loop not in ("closed", "open"):
            raise ValueError(f"loop {self.loop!r}")
        self.clients = int(spec["clients"])
        self.timeout_s = float(spec["timeout_s"])
        n_warm = int(spec["warmup_per_template"])
        templates = spec["requests"]
        shares = np.array([float(t["share"]) for t in templates])
        shares = shares / shares.sum()
        rng = np.random.default_rng([seed, 7 << 20])
        if self.loop == "open":
            total = int(round(float(spec["rate_per_s"]) * seconds))
        else:
            # a closed loop sends as many as the server answers; the
            # list (warm-up included) is longer than any window can use
            total = int(spec.get("closed_list", closed_max)) \
                - n_warm * len(templates)
        counts = np.floor(shares * total).astype(int)
        counts[0] += total - counts.sum()
        self.warmup: list[Request] = []
        timed: list[Request] = []
        for tpl, n in zip(templates, counts.tolist()):
            reqs = _template_requests(tpl, data, n + n_warm, rng)
            self.warmup += reqs[:n_warm]
            timed += reqs[n_warm:]
        order = rng.permutation(len(timed))
        self.timed = [timed[i] for i in order]
        for tpl in templates:
            if tpl.get("window"):
                # a "now" does not jump back: the places the shuffle
                # gave the template, taken in the template's order
                places = [p for p, r in enumerate(self.timed)
                          if r.template == tpl["name"]]
                mine = [r for r in timed if r.template == tpl["name"]]
                for p, r in zip(places, mine):
                    self.timed[p] = r
        if self.loop == "open":
            # a Poisson process conditioned on its count: the same
            # number of arrivals for every seed
            due = np.sort(rng.random(len(self.timed))) * seconds
            for r, t in zip(self.timed, due.tolist()):
                r.due_s = t
        self.writes: list[Request] = []
        self.write_warmup: list[Request] = []
        self.write_clients = 0
        self.written = None
        if spec.get("writes"):
            self._make_writes(spec["writes"], data, seconds, rng)
        probe = spec.get("trace_probe")
        # three that differ, or the result cache would answer the last
        self.probes = _template_requests(probe, data, 3, rng) \
            if probe else []

    def _make_writes(self, w: dict, data, seconds: float, rng) -> None:
        per = int(w["series_per_body"])
        if data.series % per:
            raise ValueError("series_per_body must divide the series")
        blocks = data.series // per
        n = int(round(float(w["rate_per_s"]) * seconds))
        steps = -(-n // blocks)
        self.write_clients = int(w["clients"])
        # what the store must hold afterwards: [series, steps] values
        # at data.end + 1 + step * cadence_s, NaN where nothing was sent
        self.written = np.full((data.series, steps), np.nan)
        cents = rng.integers(data.cents_lo, data.cents_hi,
                             size=(n + int(w["warmup"]), per))
        for k in range(n):
            block, step = k % blocks, k // blocks
            ids = np.arange(block * per, (block + 1) * per)
            self.written[ids, step] = cents[k] / 100.0
            self.writes.append(self._put(w, data, data.metric, ids,
                                         step, cents[k],
                                         k / float(w["rate_per_s"])))
        for k in range(int(w["warmup"])):
            # the same path on a metric of its own: no answer changes
            ids = np.arange(k % blocks * per, (k % blocks + 1) * per)
            self.write_warmup.append(self._put(
                w, data, data.metric + ".warm", ids, 0, cents[n + k],
                None))

    @staticmethod
    def _put(w, data, metric, ids, step, cents, due_s) -> Request:
        ts = data.end + 1 + step * data.cadence_s
        names = [[data.tag_name(k, int(v)) for v in data.tag_ids(k, ids)]
                 for k in data.tags]
        doc = [{"metric": metric, "timestamp": ts,
                "value": int(c) / 100.0,
                "tags": dict(zip(data.tags, row))}
               for c, row in zip(cents, zip(*names))]
        return Request(w["name"], "POST", w["path"], doc, due_s)
