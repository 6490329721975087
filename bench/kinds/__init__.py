"""Configuration kinds, one module each: a configuration's ``kind`` names
the file ``bench/kinds/<kind>.py``, found by ``find`` and loaded once per
file.  Each defines ``job``, ``build``, ``fetch``, ``compare``, ``LIMITS``
and ``control``, and ``server`` and ``request`` where it is served."""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

KINDS = Path(__file__).resolve().parent
_LOADED = {}   # path -> loaded kind module


def find(name: str, directory: Path = KINDS):
    """The module ``<directory>/<name>.py`` of a configuration's ``kind``."""
    path = Path(directory).resolve() / f"{name}.py"
    if path not in _LOADED:
        if not path.is_file():
            raise SystemExit(f"no configuration kind {name!r}: each kind is "
                             f"a module bench/kinds/<kind>.py, and {path} "
                             f"does not exist")
        mod_name = f"bench_kind_{len(_LOADED)}_{name}"
        mod_spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(mod_spec)
        sys.modules[mod_name] = mod   # dataclasses look their module up
        mod_spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]
