"""Adapters to the system under test, one module per entry point, found by
the name a traffic mix gives under ``program``. Only these modules import
the program; the drivers, the readers and the references do not."""

import importlib


def load(name: str):
    """The adapter module ``programs/<name>.py``."""
    return importlib.import_module(f"{__name__}.{name}")
