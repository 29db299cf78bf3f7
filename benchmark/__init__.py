"""The benchmark: ``benchmark/README.md`` says what each file is."""

import importlib


def resolve(where: str):
    """``"module:name"`` -> the object. A configuration, a traffic mix or a
    metric names its own pieces this way, so a new one brings a file of its
    own and edits none."""
    module, _, name = where.partition(":")
    return getattr(importlib.import_module(module), name)
