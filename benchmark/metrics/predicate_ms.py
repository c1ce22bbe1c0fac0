"""The serial allocate's predicate calls per traced session, one task
against all nodes each: the program's vt.serial.predicate spans
(scheduler_helper.predicate_nodes).
None where the program records no such span."""

import progspans


def read(run):
    return progspans.read(run, "vt.serial.predicate")
