"""How a configuration brings its own data and its own judge.

A configuration file (``benchmark/configs/<name>.json``) may name the
two pieces of benchmark code that are particular to a deployment:

    "generator": "benchmark/generators/<file>.py"
    "reference": "benchmark/references/<file>.py"

Absent, they mean ``benchmark/gen.py`` and ``benchmark/reference.py``.
Every entry point of the harness (``run.py``, ``tsdproc.py``,
``control.py``, ``sweep.py``) asks this module for them and imports
neither by name, so a later PR adds a deployment the harness could not
judge before by adding files and editing none.

The contract:

- A **generator** module has ``Data(spec)``, the ``data`` section of
  the configuration file, with ``metric``, ``series``, ``points``,
  ``cadence_s``, ``t0``, ``end``, ``tag_count(tagk)``,
  ``tag_name(tagk, i)``, ``tag_ids(tagk, idx)``,
  ``tag_index(tagk, name)`` and, where a traffic file has writers,
  ``tags`` (the tag keys of a series, in order) and ``cents_lo`` /
  ``cents_hi``; and ``generate(data, seed, on_text) -> (values,
  points)``: ``on_text(bytes)`` gets the ``tsdb import`` text in order
  where one is given, ``values`` is what the reference is built over.
- A **reference** module has ``Reference(data, values, limits)`` with
  ``answer(sub) -> (tagk, names, secs, Cells)``, ``selected(sub)`` and
  the class method ``supports(sub, data)``, which raises the module's
  ``Unsupported`` for a sub-query the judge cannot answer, from the
  sub-query and the deployment's parameters alone: the harness calls it
  on every template of the traffic file before the server starts.
- **A request is judged for the window it asked.** The harness reads
  ``start`` and ``end`` (milliseconds) from the request
  (:func:`window_of`). Where they are the deployment's span
  (``1000 * data.t0`` to ``1000 * data.end``) it calls ``answer(sub)``
  and ``supports(sub, data)`` as above and lays the rows on ``data.t0``
  and the span's buckets. Any other window it hands over:
  ``answer(sub, window=(start_ms, end_ms))`` and ``supports(sub, data,
  (start_ms, end_ms))``, and lays the rows on
  ``reference.window_buckets(start_ms, end_ms, secs)``. A judge that
  answers the span alone takes the third argument all the same and
  raises ``Unsupported`` for it (``reference.span_only``), so that
  traffic with a window fails before the server starts. Where series
  are not in step, the generator's ``Data`` has
  ``point_offset_s(idx)``: the seconds after ``t0 + k * cadence_s`` at
  which each series' ``k``-th point lies (``gen.py``: None, in step).
  ``rows_to_grid``, ``compare``, ``Cells``, ``Verdict`` and
  ``Unsupported`` are taken from the named module where it defines
  them and from ``reference.py`` otherwise.
- Neither imports anything of ``opentsdb_tpu`` or of JAX, itself or
  through a file of the benchmark it imports: the judge takes nothing
  of the program, and this process must not touch the chip.

A named file is a plain name under ``benchmark/`` and a file. It is
loaded with ``importlib.util.spec_from_file_location`` under its own
stem, with ``benchmark/`` and its own directory on ``sys.path``: it can
``import reference`` or ``import gen`` and build on them instead of
copying them, and a generator's worker processes find it again by that
name. Anything else is a :class:`Failed` before the server starts.
"""

from __future__ import annotations

import ast
import importlib.util
import os
import re
import sys
import types

import gen
import reference

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

_PLAIN = re.compile(r"^benchmark/[A-Za-z0-9_.\-/]+\.py$")
_FORBIDDEN = ("opentsdb_tpu", "jax", "jaxlib")
_GENERATOR = ("Data", "generate")
_REFERENCE = ("answer", "selected", "supports")
_SHARED = ("rows_to_grid", "compare", "Cells", "Verdict", "Unsupported")


class Failed(Exception):
    """The run cannot give a result; the message says why."""


def _imports(path: str, seen: set) -> None:
    """Refuse a file that imports the program or JAX, and follow its
    imports of the benchmark's own files."""
    if path in seen:
        return
    seen.add(path)
    with open(path, "r", encoding="utf-8") as fh:
        tree = ast.parse(fh.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom) and not node.level:
            names = [node.module or ""]
        else:
            continue
        for name in names:
            top = name.split(".")[0]
            if top in _FORBIDDEN:
                raise Failed(
                    f"{os.path.relpath(path, ROOT)} imports {name!r}: "
                    f"the benchmark's generator and reference take "
                    f"nothing of the program and nothing of JAX")
            for base in (os.path.dirname(path), HERE):
                local = os.path.join(base, top + ".py")
                if os.path.isfile(local):
                    _imports(local, seen)
                    break


def _load(rel: str, what: str) -> types.ModuleType:
    if not isinstance(rel, str) or not _PLAIN.match(rel) \
            or ".." in rel.split("/"):
        raise Failed(f"the configuration's {what} {rel!r} is not a "
                     f"plain path under benchmark/")
    path = os.path.join(ROOT, rel)
    if not os.path.isfile(path):
        raise Failed(f"the configuration's {what} {rel} is not a file")
    if not os.path.realpath(path).startswith(
            os.path.realpath(HERE) + os.sep):
        raise Failed(f"the configuration's {what} {rel} leads out of "
                     f"benchmark/")
    name = os.path.basename(rel)[:-3]
    if not name.isidentifier():
        raise Failed(f"the configuration's {what} {rel}: {name!r} "
                     f"cannot be a module's name")
    have = sys.modules.get(name)
    if have is not None:
        if os.path.abspath(getattr(have, "__file__", "") or "") == path:
            return have
        raise Failed(f"the configuration's {what} {rel} would hide the "
                     f"module {name!r} that is already loaded")
    _imports(path, set())
    if os.path.dirname(path) not in sys.path:
        sys.path.append(os.path.dirname(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def _must_have(owner, names, rel: str, what: str) -> None:
    lack = [n for n in names if not callable(getattr(owner, n, None))]
    if lack:
        raise Failed(f"the configuration's {what} {rel} lacks "
                     f"{', '.join(lack)} (see benchmark/deploy.py)")


def generator_of(config: dict) -> types.ModuleType:
    """The module that makes the configuration's data."""
    rel = config.get("generator")
    if rel is None:
        return gen
    mod = _load(rel, "generator")
    _must_have(mod, _GENERATOR, rel, "generator")
    return mod


def judge_of(config: dict):
    """The configuration's judge: ``Reference`` and what compares an
    answer with it, as attributes of one object."""
    rel = config.get("reference")
    if rel is None:
        return reference
    mod = _load(rel, "reference")
    _must_have(mod, ("Reference",), rel, "reference")
    _must_have(mod.Reference, _REFERENCE, rel, "reference's Reference")
    return types.SimpleNamespace(
        Reference=mod.Reference, __file__=mod.__file__,
        **{n: getattr(mod, n, getattr(reference, n)) for n in _SHARED})


def window_of(doc: dict, data):
    """``(start_ms, end_ms)`` of a request's body where it asks a
    window of its own, None where it asks the deployment's span."""
    if "start" not in doc or "end" not in doc:
        return None
    window = int(doc["start"]), int(doc["end"])
    return None if window == reference.span_of(data) else window


def refuse_unjudged(judge, data, requests, where: str) -> None:
    """:class:`Failed` where the judge cannot answer one of
    ``requests`` (one of each template is enough), naming the template:
    before the load, not after the window."""
    for req in requests:
        subs = req.doc.get("queries") if isinstance(req.doc, dict) \
            else None
        if not subs:
            raise Failed(f"{where}: the template {req.template!r} is "
                         f"no /api/query body: nothing judges it")
        window = window_of(req.doc, data)
        if window is not None and window[0] > data.end * 1000:
            # the read-back of what the window's writers sent: a span
            # after the data's, judged whole as a deployment of its own
            # (``run.written_data``)
            window = None
        for sub in subs:
            try:
                if window is None:
                    judge.Reference.supports(sub, data)
                else:
                    judge.Reference.supports(sub, data, window)
            except judge.Unsupported as e:
                raise Failed(
                    f"{where}: the reference "
                    f"{os.path.relpath(judge.__file__, ROOT)} cannot "
                    f"answer the template {req.template!r}: {e}") \
                    from None
