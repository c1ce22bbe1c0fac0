"""Open's cache snapshot and job validation per traced session: the
program's vt.open.snapshot span (framework/session.open_session_state).
None where the program records no such span."""

import progspans


def read(run):
    return progspans.read(run, "vt.open.snapshot")
