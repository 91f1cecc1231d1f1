"""How the timed path of a cell is broken, by the cell's driver.

``faults/<driver>.py`` holds ``FAULTS = {name: Fault(plant, must, may)}``:
``plant()`` breaks the program (never the benchmark) underneath a run,
``must`` are the compared numbers that have to see it and ``may`` those
that also can; no other number may fail. ``fault_runner.py`` plants by
the name, ``test_harness_cpu.py`` runs every cell under every fault of
its driver. A driver added later brings a module of its own; one that
brings none declares no fault, and its cells fail one test that says so.
"""

import importlib
from typing import Callable, NamedTuple


class Fault(NamedTuple):
    plant: Callable[[], None]
    must: frozenset
    may: frozenset = frozenset()


def of(driver: str) -> dict:
    """The faults that ``driver`` declares; {} where it has no module."""
    module = f"{__name__}.{driver}"
    try:
        return dict(importlib.import_module(module).FAULTS)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        return {}
