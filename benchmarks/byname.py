"""A kind is a file: ``<package>/<name>.py``, found by its name. The one
mechanism by which a configuration's and a traffic file's ``kind`` reach
their code — no table stands beside the files, so a later PR adds a kind by
adding its module."""
from __future__ import annotations

import importlib
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def found(package: str) -> list:
    """The names a package holds: its modules', the private ones left out."""
    directory = os.path.join(ROOT, *package.split("."))
    return sorted(f[:-3] for f in os.listdir(directory)
                  if f.endswith(".py") and not f.startswith("_"))


def module(package: str, name, what: str):
    """The module ``<package>.<name>``; a name with no file is a
    ValueError that lists the names found."""
    names = found(package)
    if name not in names:
        raise ValueError(f"unknown {what} [{name}]; found: {names}")
    return importlib.import_module(f"{package}.{name}")
