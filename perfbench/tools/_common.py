"""Shared by the tools: find the cell and the device."""

import os

from perfbench.run import ROOT, place_compile_cache

REHEARSAL = os.path.join(ROOT, "perfbench", "tests", "rehearsal")


def open_cell(workload: str, rehearse: bool):
    """(cell, device). ``rehearse``: the tiny configurations
    of ``perfbench/tests/rehearsal`` on whatever device there is — a
    rehearsal of the tool, never a measurement."""
    place_compile_cache()
    from perfbench.device import require_chips
    from perfbench.manifest import Manifest

    manifest = Manifest(REHEARSAL if rehearse else ROOT)
    cell = manifest.cell(workload)
    device = require_chips(cell["chips"], allow_cpu=rehearse)
    print(f"device: {device}", flush=True)
    return cell, device
