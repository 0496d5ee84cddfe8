"""Loop bodies, one module per driver, found by the name a traffic file
gives (``"driver": "coupled"`` loads ``benchmark/drivers/coupled.py`` and
takes its ``Driver``). A new kind of run is a new file here."""

import importlib


def load(name):
    if not name.replace("_", "").isalnum():
        raise ValueError(f"driver name {name!r}")
    return importlib.import_module(f"benchmark.drivers.{name}").Driver
