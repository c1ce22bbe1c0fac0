"""The serial allocate's prioritize calls per traced session: the program's
vt.serial.prioritize spans (scheduler_helper.prioritize_nodes).
None where the program records no such span."""

import progspans


def read(run):
    return progspans.read(run, "vt.serial.prioritize")
