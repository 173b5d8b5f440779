"""The modules the benchmark finds by name under ``bench/``: a metric's
reader (``metrics/<name>.py``) and a configuration's family
(``families/<name>.py``, "dense" where the file names none).

A family's ``load`` makes the arch, an instance of the family's own
``Arch`` class (a derived family subclasses the one it derives from),
so an arch leads back to its family by its class's module.
"""
from __future__ import annotations

import functools
import hashlib
import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


class BenchError(RuntimeError):
    """A run that cannot be measured: it prints no result."""


@functools.cache
def load_path(path: Path):
    """The module at ``path``, executed once a process.  It is
    registered under a name made from its path, since a dataclass looks
    up its module while the class is made, and ``family_of`` finds a
    family there."""
    name = f"bench_{hashlib.sha1(str(path).encode()).hexdigest()[:12]}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _find(kind: str, what: str, name: str, root: Path):
    path = (root / kind / f"{name}.py").resolve()
    if not path.is_file():
        raise BenchError(f"{what} {name!r} has no module at {path}")
    return load_path(path)


def load_reader(name: str, root: Path = BENCH):
    return _find("metrics", "metric", name, root)


def load_family(name: str, root: Path = BENCH):
    return _find("families", "family", name, root)


def load_arch(path: Path, root: Path = BENCH):
    """The configuration file at ``path``, read by its family."""
    family = json.loads(Path(path).read_text()).get("family", "dense")
    return load_family(family, root).load(path)


def family_of(arch):
    """The family whose ``load`` made ``arch``."""
    return sys.modules[type(arch).__module__]
