"""Finding the benchmark's parts by name: ``bench/<kind>/<name>.py``.

A configuration's graph generator (``generators/``), a traffic mix's
driver (``drivers/``) and a per-layer metric's reader (``metrics/``) are
each one file named as ``BENCHMARK.json`` and the data files name them,
so a later addition is a new file and touches none that is here.
"""
from __future__ import annotations

import importlib.util
import inspect
import os

BENCH = os.path.dirname(os.path.abspath(__file__))


class SetupError(RuntimeError):
    """The run cannot start: no chip, no program, an unknown part."""


def path(kind: str, name: str) -> str:
    return os.path.join(BENCH, kind, name + ".py")


def load(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``, or :class:`SetupError`."""
    where = path(kind, name)
    if not os.path.exists(where):
        known = sorted(f[:-3] for f in os.listdir(os.path.join(BENCH, kind))
                       if f.endswith(".py"))
        raise SetupError(f"no {kind[:-1]} {name!r}: bench/{kind}/{name}.py "
                         f"does not exist; known: {known}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", where)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def call(fn, what: str, *args, **params):
    """``fn(*args, **params)``; a parameter ``fn`` does not take, or one it
    needs and is not given, is a :class:`SetupError` naming ``what``:
    nothing a data file states is dropped."""
    try:
        inspect.signature(fn).bind(*args, **params)
    except TypeError as e:
        raise SetupError(f"{what}: {e}") from None
    return fn(*args, **params)
