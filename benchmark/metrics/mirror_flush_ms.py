"""Close's deferred cache-mirror flush per traced session: the program's
vt.close.flush_mirror span (framework.close_session).
None where the program records no such span."""

import progspans


def read(run):
    return progspans.read(run, "vt.close.flush_mirror")
