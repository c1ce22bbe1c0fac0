"""The enqueue action per traced session: the program's vt.action.enqueue
span (framework.action_span).
None where the program records no such span."""

import progspans


def read(run):
    return progspans.read(run, "vt.action.enqueue")
