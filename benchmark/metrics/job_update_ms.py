"""Close's job status updater per traced session: the program's
vt.close.job_updater span (framework.close_session).
None where the program records no such span."""

import progspans


def read(run):
    return progspans.read(run, "vt.close.job_updater")
